"""Benchmark harness — one module per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows (and tees to results/bench.csv).
A module's ``run`` may also return a JSON-serializable payload, written to
``results/BENCH_<name>.json`` — machine-readable perf tracked across PRs
(the CI uploads them as artifacts).

  bench_mcmc     paper Table 1 (task-farm MCMC)
  bench_dmc      paper Table 2 (DMC + dynamic load balancing, scaled-size)
  bench_schwarz  paper Table 3 (Boussinesq additive Schwarz speedup)
  bench_overhead paper §1/§5 (function-centric layer overhead)
  bench_runtime  executor runtime (farm speedup + cross-tier parity)
  bench_kernels  Pallas kernel suite (traffic-saving ratios)
  bench_serve    paged continuous-batching engine (tokens/s, slot scaling,
                 pages-in-use high-water, chunked-prefill anti-stall)
"""
import json
import os
import sys
import traceback


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    from benchmarks import (bench_dmc, bench_kernels, bench_mcmc,
                            bench_overhead, bench_runtime, bench_schwarz,
                            bench_serve)
    mods = {"mcmc": bench_mcmc, "dmc": bench_dmc, "schwarz": bench_schwarz,
            "overhead": bench_overhead, "runtime": bench_runtime,
            "kernels": bench_kernels, "serve": bench_serve}
    rows = ["name,us_per_call,derived"]
    payloads: dict[str, object] = {}
    failed: list[str] = []
    for name, mod in mods.items():
        if only and name != only:
            continue
        try:
            payload = mod.run(rows)
            if payload is not None:
                payloads[name] = payload
        except Exception as e:
            traceback.print_exc()
            failed.append(name)
            rows.append(f"{name},FAILED,{type(e).__name__}: {e}")
    out = "\n".join(rows)
    print(out)
    os.makedirs("results", exist_ok=True)
    with open("results/bench.csv", "w") as f:
        f.write(out + "\n")
    for name, payload in payloads.items():
        path = f"results/BENCH_{name}.json"
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"[bench] wrote {path}")
    if failed:
        # a failed bench must never exit green, alone or in the whole suite
        sys.exit(f"[bench] failed: {', '.join(failed)}")


if __name__ == '__main__':
    main()
