"""The system under test for a Qwen configuration: the program's model and
serving engine, built by the launcher's own calls, holding the benchmark's
weights.

This is the one file of the benchmark that knows the program's parameter
tree.  The weights are made by :func:`bench.models.qwen.make_weights` and
laid into that tree inside the same jitted call, directly into the
shardings the engine serves them with.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.models import qwen as Q

# the program's rmsnorm epsilon (``repro.models.layers.rmsnorm``)
PROGRAM_RMS_EPS = 1e-6


def model_config(c: dict):
    """The program's ``ModelConfig`` for config file ``c``."""
    from repro.configs import get_config
    m = Q.dims(c)
    if m["eps"] != PROGRAM_RMS_EPS:
        raise ValueError(f"rms_norm_eps {m['eps']} differs from the "
                         f"program's {PROGRAM_RMS_EPS}")
    return get_config(c["program_arch"]).replace(
        n_layers=m["L"], d_model=m["d"], n_heads=m["hq"], n_kv_heads=m["hkv"],
        head_dim=m["hd"], d_ff=m["f"], vocab=m["v"], qkv_bias=m["bias"],
        qk_norm=m["qk_norm"], rope_theta=m["theta"], dtype=c["torch_dtype"])


def to_program(c: dict, w, padded_vocab: int):
    """The benchmark's weights in the program's parameter tree."""
    import jax.numpy as jnp
    m = Q.dims(c)
    L, d, hq, hkv, hd = m["L"], m["d"], m["hq"], m["hkv"], m["hd"]
    lay = w["layers"]
    attn = {"wq": lay["wq"].reshape(L, d, hq, hd),
            "wk": lay["wk"].reshape(L, d, hkv, hd),
            "wv": lay["wv"].reshape(L, d, hkv, hd),
            "wo": lay["wo"].reshape(L, hq, hd, d)}
    if m["bias"]:
        attn.update(bq=lay["bq"].reshape(L, hq, hd),
                    bk=lay["bk"].reshape(L, hkv, hd),
                    bv=lay["bv"].reshape(L, hkv, hd))
    if m["qk_norm"]:
        attn.update(q_norm={"scale": lay["q_norm"]},
                    k_norm={"scale": lay["k_norm"]})
    pad = padded_vocab - m["v"]
    return {
        "embed": {"table": jnp.pad(w["embed"], ((0, pad), (0, 0)))},
        "blocks": {"ln1": {"scale": lay["ln1"]}, "attn": attn,
                   "ln2": {"scale": lay["ln2"]},
                   "mlp": {"gate": lay["wg"], "up": lay["wu"],
                           "down": lay["wd"]}},
        "final_norm": {"scale": w["final_norm"]},
        "unembed": {"w": jnp.pad(Q.head_matrix(w), ((0, 0), (0, pad)))},
    }


def make_mesh(tp: int):
    from repro.launch.mesh import make_mesh as program_mesh
    return program_mesh((tp,), ("model",)) if tp > 1 else None


def build_engine(c: dict, engine_knobs: dict, seed: int, *, tp: int = 1):
    """``ServeEngine`` serving config ``c`` with the seed's weights.

    ``engine_knobs`` are the deployment's settings (slots, lengths, pool);
    every knob not named keeps the program's default."""
    from repro.models.api import build_model
    from repro.serve import ServeEngine
    from repro.serve.engine import serve_param_specs
    cfg = model_config(c)
    model = build_model(cfg)
    mesh = make_mesh(tp)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    out_shardings = None
    if mesh is not None:
        specs = serve_param_specs(model, want, mesh, paged=True)
        out_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
    make = jax.jit(lambda key: to_program(c, Q.make_weights(c, key),
                                          cfg.padded_vocab),
                   out_shardings=out_shardings)
    params = make(Q.seed_key(seed))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    need = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != need:
        raise ValueError("benchmark weights do not match the program's "
                         f"parameter tree:\n{got}\nvs\n{need}")
    return ServeEngine(model, params, mesh=mesh, **engine_knobs)
