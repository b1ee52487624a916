"""Serving throughput + the paged KV-cache scaling win.

Seven comparisons on the smoke models:

1. Continuous batching vs sequential request handling (dense path): the
   tick ratio is the real batching speedup on memory-bound accelerators.
2. **Equal-KV-budget slot scaling**: with the same token budget of KV
   memory, the dense engine reserves ``max_slots x max_len`` up front and
   caps out, while the paged engine admits 2x the concurrent slots and its
   pages-in-use high-water mark stays far below the dense reservation.
3. **Chunked prefill anti-stall**: while a long prompt prefills in chunks,
   an already-live request keeps emitting a token every tick.
4. **Shared-prefix prefill reuse**: requests sharing a 192-token system
   prompt, prefix cache on vs off at the same page budget.  Cache-on
   prefills only each request's unique tail (the shared pages are matched
   in the radix index and incref'd), so prefill-token throughput rises and
   the pages-in-use high-water falls.
5. **Tensor-parallel decode scaling** (subprocess with 8 forced host
   devices): the MoE smoke config scaled to serving size, decoded by the
   tp=1 engine vs the tp=8 sharded engine.  The speedup tracks the host's
   free cores — 8 sharded device programs overlap on whatever cores exist,
   so a 2-core container shows ~1.2-1.7x while an 8-core host has 8x of
   expert-GEMM headroom.
6. **Quantized int8 KV at an equal HBM budget**: the byte budget 8
   full-precision slots cost buys the quant-on engine 2x the concurrent
   slots (3.2x fewer KV bytes/token on the f32 smoke model), with
   teacher-forced greedy agreement recorded alongside the tok/s numbers.
7. **Speculative decode** (`--spec-decode ngram`): decode tokens/s on a
   shared-prefix workload whose greedy decode is genuinely repetitive
   (the MoE smoke model falls into token loops, the bread-and-butter case
   for prompt-lookup drafting), spec-on vs spec-off at the SAME KV
   budget.  The acceptance rate is recorded alongside — the speedup is
   tokens-per-verify-window times the verify/decode cost ratio, so it
   rises with acceptance.

8. **Expert-parallel MoE decode + load-aware placement** (subprocess with
   8 forced host devices): the same scaled MoE config decoded by the
   serial engine vs the ep=2 ("expert", "model") engine (all-to-all
   dispatch/combine), with and without in-band re-placement.  Plan quality
   rides along as deterministic integer math: the max/mean rank-imbalance
   reduction ``plan_placement`` achieves on the engine's own measured
   routing window and on two synthetic hot-expert windows (adjacent-hot
   and dominant-with-zeros, the replication/eviction regime) — seeded, so
   the perf gate can hold the gains to a tight tolerance.

``run`` returns a machine-readable payload that ``benchmarks.run`` writes
to ``results/BENCH_serve.json`` so the perf trajectory is tracked across
PRs.
"""
from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from repro.configs import smoke_config
from repro.models.api import build_model
from repro.serve import ServeEngine

MAX_LEN = 128
PAGE = 16

# run in a subprocess: the host device count must be forced before jax
# initializes, and the parent bench process keeps 1 device
_TP_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import warnings; warnings.filterwarnings("ignore")
import json, time
import jax, numpy as np
from repro.configs import smoke_config
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from repro.serve import ServeEngine

cfg = smoke_config("qwen3-moe-235b-a22b").replace(
    remat="none", d_model=256, n_heads=8, n_kv_heads=8, head_dim=32,
    expert_d_ff=1024)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))

def decode_tput(mesh):
    eng = ServeEngine(model, params, max_slots=8, max_len=128, paged=True,
                      page_size=16, prefill_chunk=64, mesh=mesh)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, cfg.vocab, 8), max_new_tokens=2)
    eng.run_until_drained()                    # warm: compile both paths
    eng.finished.clear()
    warm_ticks = eng.stats["ticks"]
    for _ in range(8):
        eng.submit(rng.integers(0, cfg.vocab, 8), max_new_tokens=32)
    t0 = time.perf_counter()
    done = eng.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in done)
    eng.close()
    return {"tok_per_s": toks / dt, "tokens": toks,
            "ticks": eng.stats["ticks"] - warm_ticks}

tp1 = decode_tput(None)
tp8 = decode_tput(make_mesh((8,), ("model",)))
speedup = tp8["tok_per_s"] / tp1["tok_per_s"]
# the 2x target needs real cores behind the 8 virtual devices; record the
# verdict explicitly so the tracked artifact states its own validity
print(json.dumps({"tp1": tp1, "tp8": tp8, "speedup_x": speedup,
                  "target_2x_met": speedup >= 2.0,
                  "host_cores": os.cpu_count()}))
"""


def _forced_devices(script: str, what: str) -> dict:
    # the child measures virtual CPU devices by design: pin it to the CPU so
    # it never contends for an accelerator this process may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert out.returncode == 0, f"{what} bench failed:\n{out.stderr[-2000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def _tp_scaling() -> dict:
    return _forced_devices(_TP_SCRIPT, "tp")


# expert-parallel decode + load-aware placement, same scaled MoE config and
# forced-device protocol as the tp bench above
_EP_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import warnings; warnings.filterwarnings("ignore")
import json, time
import jax, numpy as np
from repro.configs import smoke_config
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from repro.serve import ServeEngine, identity_plan, imbalance, plan_placement

cfg = smoke_config("qwen3-moe-235b-a22b").replace(
    remat="none", d_model=256, n_heads=8, n_kv_heads=8, head_dim=32,
    expert_d_ff=1024)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))

def decode_tput(mesh, **kw):
    eng = ServeEngine(model, params, max_slots=8, max_len=128, paged=True,
                      page_size=16, prefill_chunk=64, mesh=mesh, **kw)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, cfg.vocab, 8), max_new_tokens=2)
    eng.run_until_drained()                    # warm: compile both paths
    eng.finished.clear()
    warm_ticks = eng.stats["ticks"]
    for _ in range(8):
        eng.submit(rng.integers(0, cfg.vocab, 8), max_new_tokens=32)
    t0 = time.perf_counter()
    done = eng.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in done)
    s = dict(eng.stats)
    eng.close()
    return {"tok_per_s": toks / dt, "tokens": toks,
            "ticks": s["ticks"] - warm_ticks,
            "moe_tokens_routed": s["moe_tokens_routed"],
            "moe_dropped_tokens": s["moe_dropped_tokens"],
            "expert_tokens": s["expert_tokens"],
            "expert_imbalance": s["expert_imbalance"],
            "placement_updates": s["placement_updates"]}

serial = decode_tput(None)
mesh = make_mesh((2, 1), ("expert", "model"))
ep2 = decode_tput(mesh)
ep2_placed = decode_tput(mesh, placement_interval=4)

def plan_gain(window, ep):
    # deterministic integer math: identity layout vs plan_placement on one
    # measured routing window, scored as max/mean per-rank token load
    window = np.asarray(window)
    plan = plan_placement(window, ep)
    before = imbalance(identity_plan(window.size, ep).rank_loads(window))
    after = imbalance(plan.rank_loads(window))
    return {"identity_imbalance": before, "planned_imbalance": after,
            "imbalance_gain": before / after,
            "replicated_experts": int((plan.split_q > 0).sum()),
            "evicted_experts": int((plan.slot_a < 0).sum())}

out = {"serial": serial, "ep2": ep2, "ep2_placed": ep2_placed,
       "ep2_vs_serial_x": ep2["tok_per_s"] / serial["tok_per_s"],
       "placement_overhead_x": ep2["tok_per_s"] / ep2_placed["tok_per_s"],
       # streams must be mesh- and placement-invariant, so routed/dropped
       # totals agree across all three engines; record the check's verdict
       "telemetry_invariant": (
           serial["expert_tokens"] == ep2["expert_tokens"]
           == ep2_placed["expert_tokens"]),
       "measured": plan_gain(ep2["expert_tokens"], 2),
       "skewed": plan_gain([1000, 900, 10, 10, 10, 10, 10, 10], 2),
       "dominant": plan_gain([5000, 0, 10, 10, 0, 10, 10, 10], 2),
       "host_cores": os.cpu_count()}
print(json.dumps(out))
"""


def _moe_ep_bench() -> dict:
    return _forced_devices(_EP_SCRIPT, "moe ep")


def _drain_tracking_peak(eng):
    """run_until_drained, recording the peak number of live slots."""
    peak = 0
    for _ in range(10_000):
        busy = eng.tick()
        peak = max(peak, len(eng.sched.live_slots()))
        if not busy and not eng.sched.has_work():
            break
    return peak


def _throughput(model, params, slots: int, *, paged: bool, n_req: int = 8,
                max_new: int = 16, num_pages=None, kv_quant=None):
    eng = ServeEngine(model, params, max_slots=slots, max_len=MAX_LEN,
                      paged=paged, page_size=PAGE, num_pages=num_pages,
                      prefill_chunk=32, kv_quant=kv_quant)
    rng = np.random.default_rng(0)
    for _ in range(n_req):
        eng.submit(rng.integers(0, model.cfg.vocab, 8), max_new_tokens=max_new)
    t0 = time.perf_counter()
    peak = _drain_tracking_peak(eng)
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in eng.finished)
    eng.close()
    return {"tok_per_s": toks / dt, "ticks": eng.stats["ticks"],
            "tokens": toks, "peak_slots": peak,
            "pages_high_water": eng.pool.high_water if eng.pool else None,
            "preemptions": eng.stats["preemptions"]}


def _shared_prefix(model, params, *, prefix_cache: bool, n_req: int = 8,
                   prefix_len: int = 192, tail_len: int = 8):
    """Prefill-token throughput on a shared-system-prompt workload
    (prefill-dominated: a long shared prefix, two decode tokens each).

    One untimed request warms the jit shapes AND (cache-on) seeds the
    prefix index — the steady state of production traffic.  The timed
    requests then measure how fast prompt tokens become resident KV.
    """
    max_len = 2 * MAX_LEN
    eng = ServeEngine(model, params, max_slots=2, max_len=max_len,
                      paged=True, page_size=PAGE, prefill_chunk=32,
                      num_pages=2 * max_len // PAGE,
                      prefix_cache=prefix_cache)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, model.cfg.vocab, prefix_len).tolist()
    prompts = [shared + rng.integers(0, model.cfg.vocab, tail_len).tolist()
               for _ in range(n_req + 1)]
    eng.submit(prompts[0], max_new_tokens=2)        # warm compile + cache
    eng.run_until_drained()
    eng.finished.clear()
    t0 = time.perf_counter()
    for p in prompts[1:]:
        eng.submit(p, max_new_tokens=2)
    done = eng.run_until_drained()
    dt = time.perf_counter() - t0
    prompt_toks = sum(len(p) for p in prompts[1:])
    assert len(done) == n_req and all(r.error is None for r in done)
    s = eng.stats
    eng.close()
    return {"prefill_tok_per_s": prompt_toks / dt, "prompt_tokens": prompt_toks,
            "pages_high_water": s["pages_high_water"],
            "prefix_hits": s["prefix_hits"],
            "prefix_hit_tokens": s["prefix_hit_tokens"],
            "cow_copies": s["cow_copies"], "evictions": s["evictions"]}


def _prefill_stall(model, params, *, paged: bool):
    """Tokens a live request emits during a 96-token prompt's prefill."""
    eng = ServeEngine(model, params, max_slots=2, max_len=MAX_LEN,
                      paged=paged, page_size=PAGE, prefill_chunk=16,
                      chunks_per_tick=1)
    eng.submit([3, 1, 4], max_new_tokens=64)
    eng.run_until_drained(max_ticks=2)          # short request is live
    short = eng.sched.slot_req[0]
    eng.submit(list(range(1, 97)), max_new_tokens=2)
    long_req = eng.queue[-1]
    n0 = len(short.output)
    ticks = 0
    while not long_req.output and ticks < 30:
        eng.tick()
        ticks += 1
    emitted = len(short.output) - n0
    eng.close()
    return {"ticks_to_long_first_token": ticks,
            "short_tokens_during_prefill": emitted}


def _spec_history_prompts(model, params, *, slots, max_len, n_req):
    """A growing-chat-history workload: each prompt is a base prompt plus
    the model's OWN previous greedy turn (one untimed generation pass) —
    the re-serving scenario where the continuation is maximally
    predictable from the visible stream, which is prompt-lookup
    drafting's bread-and-butter case."""
    rng = np.random.default_rng(0)
    shared = rng.integers(0, model.cfg.vocab, 24)
    bases = [np.concatenate([shared, rng.integers(0, model.cfg.vocab, 4)])
             for _ in range(n_req)]
    eng = ServeEngine(model, params, max_slots=slots, max_len=max_len,
                      paged=True, page_size=PAGE, prefill_chunk=64,
                      num_pages=slots * max_len // PAGE, prefix_cache=False)
    for b in bases:
        eng.submit(b, max_new_tokens=96)
    done = eng.run_until_drained()
    eng.close()
    return [np.concatenate([bases[r.rid], np.asarray(r.output, np.int32)])
            for r in done]


def _spec_decode(model, params, prompts, *, spec: bool, max_new: int = 96,
                 spec_k: int = 8, slots: int = 4, max_len: int = 512):
    """Decode tokens/s with speculative ngram drafting on vs off, equal KV
    budget.  The MoE smoke model's greedy decode settles into repetitive
    token loops — exactly the regime prompt-lookup drafting targets.  The
    prefix cache stays off (orthogonal feature) so every request prefills
    with identical chunk shapes: the warm pass below compiles every
    prefill / decode / verify-width shape the timed phase will hit."""
    from repro.serve.spec import NgramDrafter
    eng = ServeEngine(model, params, max_slots=slots, max_len=max_len,
                      paged=True, page_size=PAGE, prefill_chunk=64,
                      num_pages=slots * max_len // PAGE, prefix_cache=False,
                      spec_decode=NgramDrafter() if spec else None,
                      spec_k=spec_k)
    for p in prompts[:2]:   # warm: all jit shapes, both verify widths
        eng.submit(p, max_new_tokens=max_new)
    eng.run_until_drained()
    eng.finished.clear()
    # one admission wave (len(prompts) == slots), prefill untimed: the
    # metric is DECODE tokens/s, so the clock starts once every slot is
    # live and counts only tokens emitted from then on
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    while eng.queue or eng.sched.prefilling_slots():
        eng.tick()
    live = [eng.sched.slot_req[s] for s in eng.sched.live_slots()]
    t0_tokens = sum(len(r.output) for r in live)
    ticks0 = eng.stats["ticks"]
    t0 = time.perf_counter()
    done = eng.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in done) - t0_tokens
    assert len(done) == len(prompts) and all(r.error is None for r in done)
    s = dict(eng.stats)
    eng.close()
    return {"tok_per_s": toks / dt, "tokens": toks,
            "ticks": s["ticks"] - ticks0,
            "draft_proposed": s["draft_proposed"],
            "draft_accepted": s["draft_accepted"],
            "acceptance_rate": s["acceptance_rate"]}


def _kv_quant_bench(model, params):
    """int8 KV pages at an equal HBM budget.

    The budget is what 8 full-precision slots of ``MAX_LEN`` tokens cost in
    KV bytes.  The quant-off engine spends it on 8 slots; the quant-on
    engine's pages are 3.2x smaller (int8 values + f32 per-row scales vs
    f32 values), so the same bytes hold 2x the slots (capped at 16 here to
    bound CPU runtime — the affordable count is recorded separately) and
    the same request wave runs at twice the concurrency.

    Accuracy rides along: teacher-forced greedy agreement (same prompt,
    first sampled token, the deterministic gate the tests enforce at 0.95)
    over 48 prompts, quant-on vs quant-off.
    """
    from repro.serve.quant import kv_bytes_per_token, make_kv_quant
    bpt_off = kv_bytes_per_token(model.paged_leaf_specs())
    bpt_on = kv_bytes_per_token(
        model.paged_leaf_specs(make_kv_quant("int8")))
    budget_tokens = 8 * MAX_LEN
    budget_bytes = budget_tokens * bpt_off
    pages_off = budget_tokens // PAGE
    pages_on = budget_bytes // (bpt_on * PAGE)
    slots_affordable = (pages_on * PAGE) // MAX_LEN
    slots_on = min(16, slots_affordable)

    off = _throughput(model, params, 8, paged=True, n_req=16,
                      num_pages=pages_off)
    on = _throughput(model, params, slots_on, paged=True, n_req=16,
                     num_pages=pages_on, kv_quant="int8")

    def first_tokens(kv_quant):
        eng = ServeEngine(model, params, max_slots=8, max_len=MAX_LEN,
                          paged=True, page_size=PAGE, kv_quant=kv_quant)
        rng = np.random.default_rng(1)
        for _ in range(48):
            plen = int(rng.integers(4, 60))
            eng.submit(rng.integers(0, model.cfg.vocab, plen),
                       max_new_tokens=1)
        done = eng.run_until_drained()
        eng.close()
        return {r.rid: r.output[0] for r in done}

    a, b = first_tokens(None), first_tokens("int8")
    match = sum(a[r] == b[r] for r in a) / len(a)
    slot_x = on["peak_slots"] / max(off["peak_slots"], 1)
    return {
        "bytes_per_token": {"off": bpt_off, "int8": bpt_on,
                            "ratio_x": bpt_off / bpt_on},
        "equal_hbm": {
            "budget_bytes": budget_bytes,
            "off": dict(off, slots=8, num_pages=pages_off),
            "int8": dict(on, slots=slots_on, num_pages=pages_on),
            "slots_affordable_int8": slots_affordable,
            "slot_scaling_x": slot_x,
            "target_1p8x_met": slot_x >= 1.8,
        },
        "token_match": {"n": len(a), "match_rate": match,
                        "target_0p95_met": match >= 0.95},
    }


def _traffic_bench(model, params):
    """Open-loop traffic: Poisson and bursty arrivals at two load levels.

    Every number before this came from "submit everything, drain" — no
    arrival process, so no queueing delay and no latency distribution.
    Here the harness submits at seeded arrival times under the virtual
    clock, so TTFT/ITL/e2e percentiles and SLO goodput are measured in
    TICKS and are a deterministic function of the seed: the perf gate can
    hold them to a tight tolerance because only a real scheduling change
    (not runner noise) moves them.  Wall seconds ride along untracked.
    """
    from repro.serve.traffic import make_workload, run_traffic
    out = {}
    for kind in ("poisson", "bursty"):
        for label, rate in (("low", 0.25), ("high", 1.0)):
            wl = make_workload(kind=kind, n_requests=16, rate=rate,
                               vocab=model.cfg.vocab, seed=7,
                               max_new_tokens=8, shared_prefix_len=8,
                               n_sessions=2)
            eng = ServeEngine(model, params, max_slots=4, max_len=MAX_LEN,
                              paged=True, page_size=PAGE, prefill_chunk=32)
            t0 = time.perf_counter()
            res = run_traffic(eng, wl, slo={"ttft": 24.0, "e2e": 96.0})
            dt = time.perf_counter() - t0
            eng.close()
            rep = res["report"]
            out[f"{kind}_{label}"] = {
                "rate": rate, "n_requests": rep["n_requests"],
                "tokens": rep["tokens"], "span_ticks": rep["span"],
                "wall_seconds": dt,
                "ttft": rep["ttft"], "itl": rep["itl"], "e2e": rep["e2e"],
                "tok_per_tick": rep["tok_per_s"], "goodput": rep["goodput"],
            }
    return out


def _paged_kernel_microbench(*, B=4, Hq=4, Hkv=2, D=32, ps=16, P=4,
                             iters=20):
    """Fused multi-query paged-attention kernel vs the jnp gather fallback,
    at the decode (W=1) and spec-verify (W=8) window shapes the engine
    actually issues.  Both sides are jitted and warmed; calls/s per path.

    Off-TPU the Pallas side runs interpret=True (Python-evaluated grid), so
    the kernel-vs-fallback RATIO is only meaningful on a real TPU — the
    ``interpreted`` flag is recorded so the tracked artifact states its own
    validity, and the perf gate watches each path's absolute calls/s for
    cliffs rather than the cross-path ratio.
    """
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.models import attention as A

    N = B * P + 1                            # live pages + trash page
    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.normal(size=(N, ps, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, ps, Hkv, D)), jnp.float32)
    tables = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    fallback = jax.jit(functools.partial(A.paged_window_attention,
                                         use_pallas=False))
    kernel = kops.paged_attention_mq

    def time_path(fn, q, lens):
        fn(q, kp, vp, tables, lens).block_until_ready()     # warm/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(q, kp, vp, tables, lens)
        out.block_until_ready()
        return iters / (time.perf_counter() - t0)

    out = {"interpreted": jax.default_backend() != "tpu",
           "shape": {"B": B, "Hq": Hq, "Hkv": Hkv, "D": D,
                     "page_size": ps, "pages_per_seq": P}}
    for name, W in (("decode", 1), ("verify", 8)):
        q = jnp.asarray(rng.normal(size=(B, W, Hq, D)), jnp.float32)
        lens = jnp.asarray(rng.integers(1, P * ps - W + 1, size=B),
                           jnp.int32)
        kern = time_path(kernel, q, lens)
        # fallback takes n_cached (= kernel lengths - 1)
        fb = time_path(fallback, q, lens - 1)
        out[name] = {"window": W, "kernel_calls_per_s": kern,
                     "fallback_calls_per_s": fb,
                     "kernel_vs_fallback_x": kern / fb}
    return out


def run(csv_rows: list):
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _throughput(model, params, 2, paged=False, n_req=2, max_new=4)  # warm
    _throughput(model, params, 2, paged=True, n_req=2, max_new=4,
                num_pages=2 * MAX_LEN // PAGE)

    seq = _throughput(model, params, 1, paged=False)
    cb = _throughput(model, params, 4, paged=False)
    csv_rows.append(
        f"serve_sequential,{1e6/seq['tok_per_s']:.0f},"
        f"tok_per_s={seq['tok_per_s']:.1f};decode_ticks={seq['ticks']}")
    # On memory-bound accelerators a decode tick's cost is ~flat in batch, so
    # the tick ratio is the real continuous-batching speedup; CPU tok/s is
    # compute-bound and does not show it.
    csv_rows.append(
        f"serve_continuous4,{1e6/cb['tok_per_s']:.0f},"
        f"tok_per_s={cb['tok_per_s']:.1f};decode_ticks={cb['ticks']};"
        f"ticks_saved={seq['ticks']/cb['ticks']:.2f}x")

    # equal KV budget: 4 dense slots' worth of pages, 2x the slots paged
    budget_tokens = 4 * MAX_LEN
    dense = _throughput(model, params, 4, paged=False)
    paged = _throughput(model, params, 8, paged=True,
                        num_pages=budget_tokens // PAGE)
    csv_rows.append(
        f"serve_paged8_equal_budget,{1e6/paged['tok_per_s']:.0f},"
        f"tok_per_s={paged['tok_per_s']:.1f};decode_ticks={paged['ticks']};"
        f"peak_slots={paged['peak_slots']}vs{dense['peak_slots']};"
        f"pages_hw={paged['pages_high_water']}"
        f"of{budget_tokens // PAGE}")

    stall = _prefill_stall(model, params, paged=True)
    csv_rows.append(
        f"serve_chunked_prefill,{stall['ticks_to_long_first_token']},"
        f"short_tokens_during_96tok_prefill="
        f"{stall['short_tokens_during_prefill']}")

    pc_on = _shared_prefix(model, params, prefix_cache=True)
    pc_off = _shared_prefix(model, params, prefix_cache=False)
    pc_speedup = pc_on["prefill_tok_per_s"] / pc_off["prefill_tok_per_s"]
    csv_rows.append(
        f"serve_prefix_cache,{1e6/pc_on['prefill_tok_per_s']:.0f},"
        f"prefill_tok_per_s={pc_on['prefill_tok_per_s']:.1f};"
        f"off={pc_off['prefill_tok_per_s']:.1f};"
        f"speedup={pc_speedup:.2f}x;"
        f"pages_hw_on={pc_on['pages_high_water']};"
        f"pages_hw_off={pc_off['pages_high_water']};"
        f"hit_tokens={pc_on['prefix_hit_tokens']}")

    kvq = _kv_quant_bench(model, params)
    eq = kvq["equal_hbm"]
    csv_rows.append(
        f"serve_kv_quant_int8,{1e6/eq['int8']['tok_per_s']:.0f},"
        f"tok_per_s={eq['int8']['tok_per_s']:.1f};"
        f"off={eq['off']['tok_per_s']:.1f};"
        f"bytes_per_token={kvq['bytes_per_token']['int8']}"
        f"vs{kvq['bytes_per_token']['off']};"
        f"slots_equal_hbm={eq['int8']['peak_slots']}"
        f"vs{eq['off']['peak_slots']};"
        f"token_match={kvq['token_match']['match_rate']:.3f}")

    traffic = _traffic_bench(model, params)
    for key in ("poisson_high", "bursty_high"):
        t = traffic[key]
        csv_rows.append(
            f"serve_traffic_{key},{t['ttft']['p99']:.0f},"
            f"ttft_p99_ticks={t['ttft']['p99']:.1f};"
            f"ttft_p50={t['ttft']['p50']:.1f};"
            f"goodput_tok_per_tick={t['goodput']['tok_per_s']:.3f};"
            f"slo_attainment={t['goodput']['slo_attainment']:.2f};"
            f"wall_s={t['wall_seconds']:.2f}")

    moe_cfg = smoke_config("qwen3-moe-235b-a22b").replace(remat="none")
    moe_model = build_model(moe_cfg)
    moe_params = moe_model.init(jax.random.PRNGKey(0))
    spec_prompts = _spec_history_prompts(moe_model, moe_params, slots=4,
                                         max_len=512, n_req=4)
    spec_off = _spec_decode(moe_model, moe_params, spec_prompts, spec=False)
    spec_on = _spec_decode(moe_model, moe_params, spec_prompts, spec=True)
    spec_speedup = spec_on["tok_per_s"] / spec_off["tok_per_s"]
    csv_rows.append(
        f"serve_spec_decode,{1e6/spec_on['tok_per_s']:.0f},"
        f"tok_per_s={spec_on['tok_per_s']:.1f};"
        f"off={spec_off['tok_per_s']:.1f};"
        f"speedup={spec_speedup:.2f}x;"
        f"acceptance_rate={spec_on['acceptance_rate']:.2f};"
        f"ticks={spec_on['ticks']}vs{spec_off['ticks']}")

    pk = _paged_kernel_microbench()
    csv_rows.append(
        f"serve_paged_kernel_decode,{1e6/pk['decode']['kernel_calls_per_s']:.0f},"
        f"kernel_calls_per_s={pk['decode']['kernel_calls_per_s']:.1f};"
        f"fallback={pk['decode']['fallback_calls_per_s']:.1f};"
        f"interpreted={pk['interpreted']}")
    csv_rows.append(
        f"serve_paged_kernel_verify8,{1e6/pk['verify']['kernel_calls_per_s']:.0f},"
        f"kernel_calls_per_s={pk['verify']['kernel_calls_per_s']:.1f};"
        f"fallback={pk['verify']['fallback_calls_per_s']:.1f};"
        f"interpreted={pk['interpreted']}")

    tp = _tp_scaling()
    csv_rows.append(
        f"serve_tp8_moe_decode,{1e6/tp['tp8']['tok_per_s']:.0f},"
        f"tok_per_s={tp['tp8']['tok_per_s']:.1f};"
        f"tp1={tp['tp1']['tok_per_s']:.1f};"
        f"speedup={tp['speedup_x']:.2f}x_on_{os.cpu_count()}cores")

    ep = _moe_ep_bench()
    csv_rows.append(
        f"serve_moe_ep2_decode,{1e6/ep['ep2']['tok_per_s']:.0f},"
        f"tok_per_s={ep['ep2']['tok_per_s']:.1f};"
        f"serial={ep['serial']['tok_per_s']:.1f};"
        f"placed={ep['ep2_placed']['tok_per_s']:.1f};"
        f"skew_gain={ep['skewed']['imbalance_gain']:.2f}x;"
        f"dominant_gain={ep['dominant']['imbalance_gain']:.2f}x;"
        f"dropped={ep['ep2']['moe_dropped_tokens']}")

    return {
        "sequential": seq, "continuous4": cb,
        "dense_equal_budget": dense, "paged_equal_budget": paged,
        "dense_reserved_pages": budget_tokens // PAGE,
        "budget_tokens": budget_tokens,
        "chunked_prefill": stall,
        "slot_scaling_x": paged["peak_slots"] / max(dense["peak_slots"], 1),
        "prefix_cache": {
            "on": pc_on, "off": pc_off, "speedup_x": pc_speedup,
            "target_1p5x_met": pc_speedup >= 1.5,
            "high_water_reduced": (pc_on["pages_high_water"]
                                   < pc_off["pages_high_water"]),
        },
        "spec_decode": {
            "on": spec_on, "off": spec_off, "speedup_x": spec_speedup,
            "target_1p5x_met": spec_speedup >= 1.5,
        },
        "kv_quant": kvq,
        "traffic": traffic,
        "paged_kernel": pk,
        "tp_scaling": tp,
        "moe_ep": ep,
    }
