"""Serving launcher: continuous-batching engine demo.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
        --requests 16 --max-new 24

Tensor-parallel serving over a device mesh (shards attention heads, MLP ff,
experts, the vocab and the paged-KV head axis over ``tp`` devices; the
scheduler and page tables stay on the host).  MoE families can ALSO
partition whole experts over an ``ep``-sized "expert" axis (all-to-all
dispatch/combine, per-expert token telemetry, optional load-aware
re-placement with ``--expert-placement N``).  On CPU, prefix with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to fake the devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-235b-a22b \
        --smoke --mesh tp=2,ep=4 --requests 8 --max-new 16
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs, smoke_config
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from repro.serve import DisaggServeEngine, ServeEngine, make_workload, \
    run_traffic
from repro.serve.engine import serve_param_specs

# the source checkout this module runs from (<repo>/src/repro/launch/serve.py)
_SOURCE_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left to JAX.  Otherwise the cache lives at ``<repo>/.jax_cache`` of the
    source checkout this module runs from; an installed package has no
    checkout, and then the variable must be set.  Entry points call this
    once before their first compile; importing a module never touches the
    cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if not (_SOURCE_ROOT / "pyproject.toml").is_file():
        raise RuntimeError(
            f"{_SOURCE_ROOT} is not a repro checkout; set "
            "JAX_COMPILATION_CACHE_DIR to choose the compile cache directory")
    path = str(_SOURCE_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def init_model(cfg, *, seed: int = 0, mesh=None, paged: bool = True):
    """``build_model(cfg)`` plus random weights from ``PRNGKey(seed)``.

    Under a serving ``mesh`` the weights are created directly into the
    shardings the engine serves them with, so no device ever holds the whole
    tree (a 7B model in float32 does not fit one 16 GB chip).  ``paged``
    says whether the engine will serve paged (head-sharded TP) or dense
    (slot-parallel, params replicated)."""
    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    out_shardings = None
    if mesh is not None:
        specs = serve_param_specs(
            model, jax.eval_shape(model.init, key), mesh,
            paged=paged and model.supports_paged_decode())
        out_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
    return model, jax.jit(model.init, out_shardings=out_shardings)(key)


def parse_mesh(spec: str | None):
    """``"tp=N[,ep=M]"`` -> serving mesh (None -> no mesh).

    ``tp=N`` alone keeps the legacy 1-D ("model",) mesh; any spec naming
    ``ep`` builds the 2-D ("expert", "model") mesh of ep x tp devices
    (``ep=M`` alone means tp=1) — MoE experts partition over "expert",
    everything Megatron-ish over "model"."""
    if not spec:
        return None
    vals: dict[str, int] = {}
    for part in spec.split(","):
        key, _, val = part.partition("=")
        if key not in ("tp", "ep") or key in vals \
                or not val.isdigit() or int(val) < 1:
            raise SystemExit(
                f"--mesh expects tp=N[,ep=M] (each >= 1), got {spec!r}")
        vals[key] = int(val)
    tp, ep = vals.get("tp", 1), vals.get("ep")
    need = tp * (ep or 1)
    n = len(jax.devices())
    if need > n:
        raise SystemExit(f"--mesh {spec} needs {need} devices but only {n} "
                         "visible (set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N on CPU)")
    if ep is None:
        return make_mesh((tp,), ("model",))
    return make_mesh((ep, tp), ("expert", "model"))


def encoder_workload_kwargs(cfg, args) -> dict:
    """--image/--audio -> the multimodal band of :func:`make_workload`
    (empty dict when both flags are off, keeping the text schedule
    byte-identical)."""
    if getattr(args, "image", False):
        return dict(encoder="image",
                    encoder_shape=(cfg.n_image_tokens, cfg.d_model),
                    encoder_frac=args.encoder_frac, n_encoder_inputs=2)
    if getattr(args, "audio", False):
        # enc-dec rejects text-only submissions (nothing to cross-attend
        # into), so every request carries a clip
        return dict(encoder="audio",
                    encoder_shape=(cfg.n_audio_frames, cfg.d_model),
                    encoder_frac=1.0, n_encoder_inputs=2)
    return {}


def run_traffic_demo(eng, cfg, args) -> None:
    """Open-loop traffic run: seeded workload, event log, metric report."""
    slo = {}
    if args.slo_ttft is not None:
        slo["ttft"] = args.slo_ttft
    if args.slo_e2e is not None:
        slo["e2e"] = args.slo_e2e
    # cap prompt bands so prefix + tail + generation (and a VLM's image
    # pseudo-token prefix) fit in max_len
    enc_extra = cfg.n_image_tokens if args.image else 0
    hi = max(5, args.max_len - args.shared_prefix - args.max_new - 1
             - enc_extra)
    len_mix = ((3.0, 4, min(24, hi)), (1.0, min(32, hi), hi))
    wl = make_workload(kind=args.traffic, n_requests=args.requests,
                       rate=args.rate, vocab=cfg.vocab, seed=0,
                       max_new_tokens=args.max_new,
                       shared_prefix_len=args.shared_prefix, n_sessions=2,
                       len_mix=len_mix,
                       **encoder_workload_kwargs(cfg, args))
    t0 = time.perf_counter()
    res = run_traffic(eng, wl, clock=args.clock, slo=slo or None)
    dt = time.perf_counter() - t0
    eng.close()
    rep = res["report"]
    unit = "ticks" if args.clock == "virtual" else "s"
    print(f"[serve] traffic {args.traffic} rate={args.rate}: "
          f"{rep['n_measured']}/{rep['n_requests']} requests, "
          f"{rep['tokens']} tokens over {rep['span']:.1f} {unit} "
          f"({dt:.2f}s wall)"
          + (f" [disagg executor={args.executor}]" if args.disagg else ""))
    for name in ("ttft", "itl", "e2e"):
        p = rep[name]
        print(f"[serve] {name}: p50={p['p50']} p95={p['p95']} "
              f"p99={p['p99']} {unit} (n={p['n']})")
    g = rep["goodput"]
    per = "tick" if args.clock == "virtual" else "s"
    print(f"[serve] goodput: {g['tok_per_s']:.3f} tok/{per} "
          f"slo_attainment={g['slo_attainment']:.2f}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump({"args": {"traffic": args.traffic, "rate": args.rate,
                                "clock": args.clock, "disagg": args.disagg,
                                "requests": args.requests,
                                "max_new": args.max_new},
                       "wall_seconds": dt, "report": rep}, f, indent=2)
        print(f"[serve] metrics written to {args.metrics_out}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--dense", action="store_true",
                    help="force the dense per-slot cache path")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool size (default: dense-equivalent budget)")
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--prefix-cache", choices=("on", "off"), default="on",
                    help="cross-request KV reuse: refcounted pages + radix "
                    "prefix index + copy-on-write (paged path only)")
    ap.add_argument("--shared-prefix", type=int, default=24, metavar="L",
                    help="prepend an L-token common prefix to every prompt "
                    "(a shared system prompt; 0 disables)")
    ap.add_argument("--spec-decode", default="off", metavar="ngram|self-K|off",
                    help="speculative multi-token decode: a drafter proposes "
                    "tokens, one batched verify accepts the prefix the "
                    "target agrees with (paged families only; 'ngram' = "
                    "prompt-lookup, 'self-2' = first-2-layer self-draft)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens per verify window")
    ap.add_argument("--mesh", default=None, metavar="tp=N[,ep=M]",
                    help="serve tensor-parallel over an N-device "
                    "('model',) mesh; add ep=M for a 2-D "
                    "('expert', 'model') mesh partitioning whole MoE "
                    "experts over M devices (all-to-all dispatch/combine)")
    ap.add_argument("--expert-placement", type=int, default=0, metavar="N",
                    help="re-place experts every N ticks from measured "
                    "per-expert token counts (load_balance-driven, "
                    "hot-expert replication; 0 = off)")
    ap.add_argument("--pallas-attention", action="store_true", default=None,
                    help="route paged decode/verify/prefill attention "
                    "through the fused multi-query Pallas kernel off a TPU "
                    "too (interpret mode; paged families only); on a TPU "
                    "the paged path takes it unasked")
    ap.add_argument("--kv-quant", choices=("int8", "off"), default="off",
                    help="store KV pages as int8 with per-(token, head) "
                    "scale leaves, dequantized inside attention — halves "
                    "(bf16) or quarters (f32) KV bytes/token, so the same "
                    "HBM budget holds ~2-4x the concurrent slots")
    ap.add_argument("--weight-quant", choices=("int8", "off"), default="off",
                    help="store serve params as per-tensor int8, "
                    "dequantized on apply inside the jitted paged calls")
    ap.add_argument("--disagg", action="store_true",
                    help="split serving into a prefill-only engine and a "
                    "decode engine with KV page handoff between them")
    ap.add_argument("--executor", choices=("serial", "thread"),
                    default="serial",
                    help="disagg stage driver: deterministic serial order "
                    "or overlapped farm threads")
    ap.add_argument("--traffic", choices=("off", "poisson", "bursty"),
                    default="off",
                    help="drive the engine with an open-loop seeded arrival "
                    "process instead of submitting everything up front")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="traffic arrival rate (requests per clock unit)")
    ap.add_argument("--clock", choices=("virtual", "wall"), default="virtual",
                    help="virtual: 1 tick = 1 time unit, fully "
                    "deterministic; wall: real seconds")
    ap.add_argument("--slo-ttft", type=float, default=None, metavar="T",
                    help="goodput SLO: time-to-first-token bound "
                    "(clock units)")
    ap.add_argument("--slo-e2e", type=float, default=None, metavar="T",
                    help="goodput SLO: end-to-end latency bound "
                    "(clock units)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the traffic metric report as JSON")
    ap.add_argument("--image", action="store_true",
                    help="multimodal serving: attach precomputed image-patch "
                    "embeddings to a fraction of requests (VLM families; "
                    "the image prefix pages share through the prefix cache)")
    ap.add_argument("--audio", action="store_true",
                    help="multimodal serving: attach audio frames to every "
                    "request (enc-dec families; streaming chunked encode "
                    "into read-only cross-KV pages)")
    ap.add_argument("--encoder-frac", type=float, default=0.5,
                    help="fraction of requests carrying an image with "
                    "--image (audio is always 1.0 — enc-dec needs a clip)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.disagg and args.dense:
        raise SystemExit("--disagg needs the paged KV engine; drop --dense")

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family in ("hybrid",):
        raise SystemExit("engine demo targets KV-cache families; "
                         "zamba uses aligned decode (see tests)")
    if args.image and cfg.family != "vlm":
        raise SystemExit(f"--image needs a VLM arch (family 'vlm'); "
                         f"{args.arch} is '{cfg.family}'")
    if args.audio and cfg.family != "audio":
        raise SystemExit(f"--audio needs an enc-dec arch (family 'audio'); "
                         f"{args.arch} is '{cfg.family}'")
    if args.audio and (args.disagg or args.dense):
        raise SystemExit("--audio serves monolithic and paged only (cross-KV "
                         "pages have no handoff or dense twin); drop "
                         "--disagg/--dense")
    if (args.image or args.audio) and args.disagg:
        raise SystemExit("--image/--audio are wired through the monolithic "
                         "engine; drop --disagg")
    mesh = parse_mesh(args.mesh)
    model, params = init_model(cfg, seed=0, mesh=mesh, paged=not args.dense)
    kw = dict(max_slots=args.slots, max_len=args.max_len,
              page_size=args.page_size, num_pages=args.num_pages,
              prefill_chunk=args.prefill_chunk,
              prefix_cache=args.prefix_cache == "on",
              spec_decode=None if args.spec_decode == "off"
              else args.spec_decode,
              spec_k=args.spec_k, mesh=mesh,
              use_pallas_attention=args.pallas_attention,
              kv_quant=None if args.kv_quant == "off" else args.kv_quant,
              weight_quant=None if args.weight_quant == "off"
              else args.weight_quant,
              placement_interval=args.expert_placement)
    if args.disagg:
        eng = DisaggServeEngine(model, params, executor=args.executor, **kw)
    else:
        eng = ServeEngine(model, params,
                          paged=False if args.dense else None, **kw)

    if args.traffic != "off":
        run_traffic_demo(eng, cfg, args)
        return

    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab, args.shared_prefix)
    enc_pool = []
    if args.image or args.audio:
        n = cfg.n_image_tokens if args.image else cfg.n_audio_frames
        # two distinct payloads, alternated: repeated-image requests share
        # prefix pages, distinct images never alias
        enc_pool = [rng.standard_normal((n, cfg.d_model)).astype(np.float32)
                    for _ in range(2)]
    t0 = time.perf_counter()
    for i in range(args.requests):
        plen = int(rng.integers(4, 48))
        prompt = np.concatenate([shared, rng.integers(0, cfg.vocab, plen)])
        kw = {"encoder_input": enc_pool[i % 2]} if enc_pool else {}
        eng.submit(prompt, max_new_tokens=args.max_new, **kw)
    done = eng.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in done)
    if args.disagg:
        s = eng.stats
        print(f"[serve] disagg: {len(done)} requests, {toks} tokens in "
              f"{dt:.2f}s ({toks/dt:.1f} tok/s); "
              f"prefill ticks={s['prefill']['ticks']} "
              f"handoffs={s['prefill']['kv_handoffs']} | "
              f"decode ticks={s['decode']['ticks']} "
              f"injections={s['decode']['kv_injections']} "
              f"preempt={s['decode']['preemptions']} "
              f"[executor={args.executor}]")
        eng.close()
        return
    ttfts = [r.first_token_at - r.submitted_at for r in done]
    mode = "dense" if not eng.paged else (
        f"paged(ps={eng.pool.page_size}, "
        f"hw={eng.stats['pages_high_water']}/{eng.pool.num_pages} pages, "
        f"prefix-cache {'on' if eng.prefix_cache else 'off'})")
    if eng.kv_quant is not None or eng.weight_quant:
        mode += (f" quant(kv={eng.stats['kv_quant']}, "
                 f"w={eng.stats['weight_quant']}, "
                 f"{eng.stats['kv_bytes_per_token']} KV B/tok)")
    if eng.drafter is not None:
        mode += f" spec={args.spec_decode}(k={eng.spec_k})"
    if mesh is not None:
        mode += f" tp={eng.tp}" + (f" ep={eng.ep}" if eng.ep > 1 else "")
    print(f"[serve] {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s); ticks={eng.stats['ticks']} "
          f"chunks={eng.stats['chunk_prefills']} "
          f"preempt={eng.stats['preemptions']} [{mode}] "
          f"mean TTFT {np.mean(ttfts)*1e3:.0f}ms")
    if eng.paged:
        s = eng.stats
        print(f"[serve] prefix cache: hits={s['prefix_hits']} "
              f"hit_tokens={s['prefix_hit_tokens']} "
              f"cow_copies={s['cow_copies']} evictions={s['evictions']} "
              f"cached_now={eng.pool.pages_cached} pages")
        if getattr(eng, "cross_pool", None) is not None:
            print(f"[serve] cross-KV: encode_chunks={s['encode_chunks']} "
                  f"pages_in_use={eng.cross_pool.pages_in_use}/"
                  f"{eng.cross_pool.num_pages}")
        if eng.drafter is not None:
            print(f"[serve] spec decode: proposed={s['draft_proposed']} "
                  f"accepted={s['draft_accepted']} "
                  f"acceptance_rate={s['acceptance_rate']:.2f}")
        if cfg.n_experts:
            # dropped = capacity-factor + placement-eviction losses, which
            # are silent in the token streams (the drop rule zeroes the
            # expert's contribution) — surface them here
            print(f"[serve] moe: routed={s['moe_tokens_routed']} "
                  f"dropped={s['moe_dropped_tokens']} "
                  f"rank_imbalance={s['expert_imbalance']:.2f} "
                  f"placements={s['placement_updates']}")


if __name__ == "__main__":
    main()
