"""Write the StableHLO text of the serving engine's programs, one file per
program and case, so that two commits can be compared program by program
(``diff -r``).

    PYTHONPATH=src python tools/engine_hlo.py OUT_DIR [CASE ...]
    XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
        PYTHONPATH=src python tools/engine_hlo.py OUT_DIR tp2

Run from the repository root.  The programs are those the benchmark runs:
``decode_paged``, ``verify_paged`` (a 5-token window), ``prefill_chunk``,
``cow_copy`` and ``lm_head``.  Cases:

* ``qwen3-1.7b``, ``qwen3-moe-ep16``: the benchmark's two configurations at
  their published widths, with the cells' slots and lengths and a pool of
  300 pages (room for one 4096-token request); arguments described on a
  TPU v5e that is not attached, so the attention kernel lowers as the
  chip runs it;
* ``smoke-dense-int8``, ``smoke-moe-int8``: SMOKE configs with int8 KV
  pages, on the CPU with the gather attention;
* ``tp2``: the SMOKE dense config on a two-device ("model",) mesh (needs
  two devices: see the second command).

The text carries no debug information.  The attention kernel's body,
which the program holds serialized with its source locations, is written
out as text without them.
"""
import base64
import importlib
import json
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parents[1]
N_PAGES, WINDOW = 300, 5


def _bench_engine(config, traffic, chip):
    """An engine for a benchmark configuration whose parameters are only
    shapes, and a function that places an array's shape on ``chip``."""
    from repro.models.api import build_model
    from repro.serve import ServeEngine
    sys.path.insert(0, str(ROOT))
    c = json.loads((ROOT / "bench/configs" / config).read_text())
    mix = json.loads((ROOT / "bench/traffic" / traffic).read_text())
    sut = importlib.import_module(f"bench.models.{c['reference']}_program")
    cfg = sut.model_config(c)
    model = build_model(cfg)
    params = jax.eval_shape(lambda k: model.init(k, dtype=cfg.param_dtype),
                            jax.random.PRNGKey(0))
    knobs = dict(c["deployment"]["engine"], **mix["engine"],
                 num_pages=N_PAGES, use_pallas_attention=True)
    eng = ServeEngine(model, params, **knobs)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)
    return eng, on_chip


def _smoke_engine(arch, mesh=None, **kw):
    from repro.configs import smoke_config
    from repro.models.api import build_model
    from repro.serve import ServeEngine
    cfg = smoke_config(arch).replace(remat="none", n_heads=8, n_kv_heads=4)
    model = build_model(cfg)
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)), max_slots=4,
                      max_len=64, page_size=8, prefill_chunk=16, mesh=mesh,
                      **kw)
    return eng, lambda tree: tree


def _programs(eng, place):
    """{program: (jitted program, its arguments)} at the engine's shapes."""
    B, P = eng.sched.table.shape
    C, ps = eng.sched.prefill_chunk, eng.pool.page_size
    d = eng.model.cfg.d_model
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)      # noqa: E731
    params, storage = place(eng.params), place(eng.pool.storage)
    pl = eng._place_arr
    win = lambda w: place((i32(B, P), i32(B), i32(B, w)))     # noqa: E731
    return {
        "decode_paged": (eng._decode_paged, (params, storage) + win(1)
                         + place((i32(B), i32(B), pl))),
        "verify_paged": (eng._verify_paged, (params, storage) + win(WINDOW)
                         + place((i32(B, WINDOW), i32(B, WINDOW), pl))),
        "prefill_chunk": (eng._prefill_chunk, (params, storage) + place(
            (i32(P), i32(C // ps), i32(), i32(1, C), pl))),
        "cow_copy": (eng._cow_copy, (storage,) + place((i32(2), i32(2)))),
        "lm_head": (eng._lm_head, (params, place(jax.ShapeDtypeStruct(
            (1, 1, d), jnp.dtype(eng.model.cfg.dtype))))),
    }


def _case(name):
    if name in ("qwen3-1.7b", "qwen3-moe-ep16"):
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        from repro.kernels import ops as kops
        kops._interpret = lambda: False     # lower the kernel for the chip
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        chip = SingleDeviceSharding(topo.devices[0])
        files = {"qwen3-1.7b": ("qwen3-1.7b.json", "decode.json"),
                 "qwen3-moe-ep16": ("qwen3-moe-235b-a22b-ep16.json",
                                    "decode-c128.json")}[name]
        return _bench_engine(*files, chip)
    if name == "smoke-dense-int8":
        return _smoke_engine("qwen2-7b", kv_quant="int8")
    if name == "smoke-moe-int8":
        return _smoke_engine("qwen3-moe-235b-a22b", kv_quant="int8")
    if name == "tp2":
        from repro.launch.mesh import make_mesh
        if jax.device_count() < 2:
            raise SystemExit("tp2 needs two devices: set XLA_FLAGS="
                             "--xla_force_host_platform_device_count=2")
        return _smoke_engine("qwen2-7b", mesh=make_mesh((2,), ("model",)))
    raise SystemExit(f"unknown case {name!r}")


def _kernel_bodies(text):
    """``text`` with each kernel's serialized body (MLIR bytecode) replaced
    by its text, without source locations."""
    from jax._src.interpreters import mlir
    from jaxlib.mlir import ir

    def body(m):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            mod = ir.Module.parse(base64.b64decode(m.group(1)))
        return "\n" + mod.operation.get_asm(enable_debug_info=False) + "\n"
    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


def main(out, cases):
    out = pathlib.Path(out)
    out.mkdir(parents=True, exist_ok=True)
    for name in cases:
        eng, place = _case(name)
        for prog, (fn, args) in _programs(eng, place).items():
            text = _kernel_bodies(fn.lower(*args).as_text(debug_info=False))
            (out / f"{name}.{prog}.mlir").write_text(text)
            print(name, prog, len(text.splitlines()), "lines", flush=True)
        eng.close()


if __name__ == "__main__":
    if len(sys.argv) < 2:
        raise SystemExit(__doc__)
    main(sys.argv[1], sys.argv[2:] or ["qwen3-1.7b", "qwen3-moe-ep16",
                                       "smoke-dense-int8", "smoke-moe-int8"])
