"""On-chip smoke check of the paged serving path (TPU only).

    python chip_smoke.py             # one chip: qwen3-1.7b, kernel off vs on
    python chip_smoke.py --chips 4   # four chips: qwen2-7b at tp=1 vs tp=4
                                     # (4 layers), then 28 layers at tp=4

Builds the model through the launcher's own calls
(``get_config`` -> ``init_model`` -> ``ServeEngine``) at published widths
with random weights from ``--seed``, serves requests through the paged
engine (prefix cache + chunked prefill) and compares two runs of the same
traffic.  The second run is teacher-forced to the first run's greedy
tokens, so both see identical contexts and their last-position logits are
comparable at every prefill and decode step.

Times printed here are smoke timings (one cold run, compilation included),
not benchmark numbers.  Without a TPU the script exits non-zero before
doing any work; it never falls back to the CPU.  The last line of a
successful run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import enable_compile_cache, init_model  # noqa: E402
from repro.serve import ServeEngine  # noqa: E402
from repro.serve.sampling import greedy  # noqa: E402

# Logit agreement bounds between two runs of the same traffic, checked at
# every prefill and decode step: per live row, max |a - b| <= rtol * max |a|.
# BF16_RTOL (kernel vs gather attention, both bf16): bf16 carries 8
# significant bits (ulp 2**-8 ~ 0.4% of scale), so this allows ~13 ulps of
# the row's largest logit for two attention orderings through every layer,
# while a wrong head, page or mask moves logits by O(1) of their scale.
# F32_RTOL (tp=4 vs tp=1, float32 at "highest" matmul precision, attention
# weights at fan-in scale, see ``fan_in_attention``): the two differ only by
# the order of float32 partial sums.  On four TPU v5e the 4-layer qwen2-7b
# cut reads 1.4e-6 (CPU: 2.8e-6), and the same comparison computed in bf16
# reads 1.4e-2, so a sharded path that silently drops to bf16 fails.
BF16_RTOL = 0.05
F32_RTOL = 1e-4


class TeacherSampler:
    """Engine-wide sampler that records what the engine feeds it.

    Without ``teacher`` it samples greedily and records, per call, the
    chosen tokens and the logits of the live rows.  With ``teacher`` (the
    recorder of an earlier run of the same traffic) it returns the
    teacher's tokens call for call — so the engine schedules and writes
    exactly what the earlier run did — and measures how far its own logits
    and greedy picks are from the teacher's: at each request's prefill
    (first token), at its 4th decode step, and over every step."""

    CHECKED_DECODE_STEP = 4

    def __init__(self, vocab: int, teacher: "TeacherSampler | None" = None):
        self.vocab = vocab
        self.teacher = teacher
        self.engine = None              # set once the engine exists
        self.recording = True
        self.calls: list[tuple] = []    # (tokens, logits, live rows)
        self.max_rel = {"prefill": 0.0, "decode4": 0.0, "all": 0.0}
        self.agree = self.total = 0

    def _point(self, row):
        if row is None:
            return "prefill"
        req = self.engine.sched.slot_req[row]
        return "decode4" if len(req.output) == self.CHECKED_DECODE_STEP \
            else None

    def __call__(self, key, logits):
        tok = greedy(logits, true_vocab=self.vocab)
        if not self.recording:
            return tok
        rows = [None] if logits.ndim == 1 \
            else list(self.engine.sched.live_slots())
        lg = np.asarray(jax.device_get(logits), np.float32)[..., :self.vocab]
        own = np.asarray(jax.device_get(tok))
        if not np.isfinite(lg if rows == [None] else lg[rows]).all():
            raise FloatingPointError("non-finite logits")
        if self.teacher is None:
            self.calls.append((own, lg, rows))
            return tok
        i = len(self.calls)
        t_tok, t_lg, t_rows = self.teacher.calls[i]
        if t_rows != rows:
            raise AssertionError(
                f"sampler call {i}: schedule diverged from the teacher run "
                f"(rows {t_rows} vs {rows})")
        self.calls.append((t_tok, None, rows))
        for r in rows:
            a = t_lg if r is None else t_lg[r]
            b = lg if r is None else lg[r]
            rel = float(np.abs(a - b).max() / max(np.abs(a).max(), 1e-30))
            for point in ("all", self._point(r)):
                if point is not None:
                    self.max_rel[point] = max(self.max_rel[point], rel)
            self.agree += int((own if r is None else own[r])
                              == (t_tok if r is None else t_tok[r]))
            self.total += 1
        return jnp.asarray(t_tok)


def fan_in_attention(params, cfg):
    """``params`` with the attention projections rescaled to std
    1/sqrt(fan-in).  The default init reads a 3-D projection's fan-in from
    its head axis (``Param.stddev`` uses ``shape[-2]``), so q/k/v/o come out
    5-30x too large at qwen2-7b widths: attention scores reach std ~300 and
    a float32 rounding difference grows ~10x every two layers.  On four TPU
    v5e the 4-layer cut's tp=4 vs tp=1 logits then differ by 2.9e-3 after
    prefill and 5.9e-2 at worst; at fan-in scale, by 1.4e-6."""
    a = dict(params["blocks"]["attn"])
    d, hq, hkv = cfg.d_model, cfg.padded_q_heads, cfg.padded_kv_heads
    a["wq"] = a["wq"] * (hq / d) ** 0.5
    a["wk"] = a["wk"] * (hkv / d) ** 0.5
    a["wv"] = a["wv"] * (hkv / d) ** 0.5
    a["wo"] = a["wo"] * hq ** -0.5
    return dict(params, blocks=dict(params["blocks"], attn=a))


def make_prompts(vocab: int, *, seed: int, n: int, prefix: int,
                 tail_lo: int, tail_hi: int):
    """``n`` prompts sharing a ``prefix``-token system prompt, each with a
    random tail of ``tail_lo..tail_hi`` tokens (seeded)."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, prefix)
    return [np.concatenate([shared,
                            rng.integers(0, vocab,
                                         int(rng.integers(tail_lo,
                                                          tail_hi + 1)))])
            for _ in range(n)]


def serve(model, params, prompts, *, max_new: int, sampler, mesh=None,
          use_pallas: bool, slots: int, max_len: int, prefill_chunk: int,
          log=print, label=""):
    """One engine, one warm-up request (compiles every shape the run uses),
    then ``prompts`` served to completion.  Returns the engine."""
    eng = ServeEngine(model, params, max_slots=slots, max_len=max_len,
                      page_size=16, prefill_chunk=prefill_chunk,
                      prefix_cache=True, use_pallas_attention=use_pallas,
                      mesh=mesh, sampler=sampler)
    sampler.engine = eng
    sampler.recording = False
    t0 = time.perf_counter()
    warm = np.arange(prefill_chunk + 3) % model.cfg.vocab + 1
    eng.submit(warm, max_new_tokens=2)
    eng.run_until_drained()
    eng.finished.clear()
    log(f"[smoke] {label} compile+warm-up: "
        f"{time.perf_counter() - t0:.1f} s (smoke timing)")
    sampler.recording = True
    t0 = time.perf_counter()
    # the first request's prefill registers the shared prefix's pages, so
    # every later request is admitted with a prefix-cache hit
    eng.submit(prompts[0], max_new_tokens=max_new)
    eng.tick()
    for p in prompts[1:]:
        eng.submit(p, max_new_tokens=max_new)
    done = eng.run_until_drained()
    jax.block_until_ready(eng.pool.storage)
    dt = time.perf_counter() - t0
    errors = [r.error for r in done if r.error is not None]
    if errors:
        raise RuntimeError(f"{label}: {len(errors)} request(s) failed") \
            from errors[0]
    toks = sum(len(r.output) for r in done)
    if len(done) != len(prompts) or toks != max_new * len(prompts):
        raise AssertionError(f"{label}: served {len(done)} requests / "
                             f"{toks} tokens, want {len(prompts)} / "
                             f"{max_new * len(prompts)}")
    s = eng.stats
    log(f"[smoke] {label} served {len(done)} requests, {toks} tokens, "
        f"{sum(len(p) for p in prompts)} prompt tokens in {dt:.2f} s "
        f"(smoke timing); ticks={s['ticks']} chunks={s['chunk_prefills']} "
        f"prefix_hits={s['prefix_hits']} "
        f"hit_tokens={s['prefix_hit_tokens']} "
        f"pages_high_water={s['pages_high_water']}")
    eng.close()
    sampler.engine = None               # let the engine (and its pool) go
    return eng


def compare_runs(a: TeacherSampler, b: TeacherSampler, what: str,
                 rtol: float, log=print) -> dict:
    """Check run ``b`` (teacher-forced by ``a``) against ``a``: the logits
    of every live row at every sampled step within ``rtol``."""
    if len(b.calls) != len(a.calls):
        raise AssertionError(f"{what}: {len(b.calls)} sampler calls vs "
                             f"{len(a.calls)} in the reference run")
    share = b.agree / max(b.total, 1)
    m = b.max_rel
    log(f"[smoke] {what}: logits max rel err after prefill="
        f"{m['prefill']:.3e}, after 4 decode steps={m['decode4']:.3e}, "
        f"over all steps={m['all']:.3e} (tolerance {rtol}); greedy "
        f"tokens agreeing {b.agree}/{b.total} = {share:.4f}")
    if not m["all"] <= rtol:
        raise AssertionError(f"{what}: logits disagree, max rel err "
                             f"{m['all']:.3e} > {rtol}")
    return {"max_rel": dict(m), "greedy_agree": share}


def kernel_parity(cfg, *, seed: int = 0, n_requests: int = 8,
                  prefix: int = 128, tail: tuple = (64, 384),
                  max_new: int = 32, slots: int = 8, max_len: int = 1024,
                  prefill_chunk: int = 64, log=print):
    """Serve the same traffic with the jnp gather attention and with the
    fused Pallas kernel; the kernel run is teacher-forced by the gather
    run.  Returns (kernel engine, comparison dict)."""
    t0 = time.perf_counter()
    model, params = init_model(cfg, seed=seed)
    jax.block_until_ready(params)
    log(f"[smoke] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}, {model.n_params() / 1e9:.3f} B params; init "
        f"{time.perf_counter() - t0:.1f} s (smoke timing)")
    prompts = make_prompts(cfg.vocab, seed=seed, n=n_requests,
                           prefix=prefix, tail_lo=tail[0], tail_hi=tail[1])
    kw = dict(max_new=max_new, slots=slots, max_len=max_len,
              prefill_chunk=prefill_chunk, log=log)
    ref = TeacherSampler(cfg.vocab)
    serve(model, params, prompts, sampler=ref, use_pallas=False,
          label="gather attention", **kw)
    gc.collect()                    # drop the first engine's KV pool
    got = TeacherSampler(cfg.vocab, teacher=ref)
    eng = serve(model, params, prompts, sampler=got, use_pallas=True,
                label="pallas kernel", **kw)
    return eng, compare_runs(ref, got, "kernel vs gather", BF16_RTOL,
                              log=log)


def decode_program_text(eng) -> str:
    """Compiled HLO of the engine's paged decode step."""
    B = eng.max_slots
    z = jnp.zeros((B,), jnp.int32)
    return eng._decode_paged.lower(
        eng.params, eng.pool.storage, jnp.asarray(eng.sched.table), z,
        jnp.zeros((B, 1), jnp.int32), z, z,
        eng._place_arr).compile().as_text()


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def one_chip(args) -> None:
    cfg = get_config("qwen3-1.7b")
    eng, _ = kernel_parity(cfg, seed=args.seed)
    t0 = time.perf_counter()
    hlo = decode_program_text(eng)
    n = hlo.count("tpu_custom_call")
    print(f"[smoke] kernel decode program: {n} tpu_custom_call op(s) "
          f"({time.perf_counter() - t0:.1f} s to fetch)")
    if n == 0:
        raise AssertionError("kernel run's decode program has no "
                             "tpu_custom_call: the kernel did not compile in")
    print(f"[smoke] peak_bytes_in_use: "
          f"{peak_bytes(jax.devices()[0]) / 2**30:.3f} GiB")


def four_chips(args) -> None:
    """tp=1 vs tp=4 on a 4-layer qwen2-7b, then all 28 layers at tp=4."""
    mesh = make_mesh((4,), ("model",))
    full = get_config("qwen2-7b")
    # The comparison checks the sharding, so it runs in float32 at full
    # matmul precision, through the jnp gather attention, with attention
    # weights at fan-in scale: tp=1 and tp=4 then differ only in the order
    # of float32 partial sums (F32_RTOL).  The full-depth run below serves
    # the launcher's weights through the kernel (one KV head per chip).
    cut = full.replace(n_layers=4, dtype="float32")
    prompts = make_prompts(cut.vocab, seed=args.seed, n=4, prefix=128,
                           tail_lo=32, tail_hi=256)
    kw = dict(max_new=16, slots=4, max_len=512, prefill_chunk=64)

    t0 = time.perf_counter()
    model, p1 = init_model(cut, seed=args.seed)
    p1 = jax.block_until_ready(fan_in_attention(p1, cut))
    print(f"[smoke] {cut.name} cut to {cut.n_layers} layers "
          f"(d_model {cut.d_model}, {cut.n_heads}/{cut.n_kv_heads} heads): "
          f"init {time.perf_counter() - t0:.1f} s (smoke timing)")
    highest = jax.default_matmul_precision("highest")
    ref = TeacherSampler(cut.vocab)
    with highest:
        serve(model, p1, prompts, sampler=ref, label="tp=1",
              use_pallas=False, **kw)
    # the same weights: the mesh engine reshards them over the mesh
    got = TeacherSampler(cut.vocab, teacher=ref)
    with highest:
        serve(model, p1, prompts, sampler=got, mesh=mesh, label="tp=4",
              use_pallas=False, **kw)
    compare_runs(ref, got, "tp=4 vs tp=1 (float32)", F32_RTOL)
    del p1, model, ref, got
    gc.collect()

    t0 = time.perf_counter()
    model, params = init_model(full, seed=args.seed, mesh=mesh)
    jax.block_until_ready(params)
    print(f"[smoke] {full.name}: {full.n_layers} layers, d_model "
          f"{full.d_model}, {model.n_params() / 1e9:.3f} B params sharded "
          f"tp=4; init {time.perf_counter() - t0:.1f} s (smoke timing)")
    rec = TeacherSampler(full.vocab)
    serve(model, params, prompts, sampler=rec, mesh=mesh,
          label="tp=4 full depth", use_pallas=True, **kw)
    peaks = [peak_bytes(d) / 2**30 for d in jax.devices()[:4]]
    print("[smoke] peak_bytes_in_use per chip: "
          + " ".join(f"{p:.3f}" for p in peaks) + " GiB")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernel parity at qwen3-1.7b; 4: tp=4 parity "
                    "and full-depth qwen2-7b at tp=4")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX sees {devs[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"[smoke] device_kind={devs[0].device_kind} count={len(devs)} "
          f"jax={jax.__version__} compile_cache={cache}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args)
    print(f"[smoke] total wall {time.perf_counter() - t0:.1f} s "
          "(smoke timing)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
