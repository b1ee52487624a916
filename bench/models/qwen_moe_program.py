"""The system under test for a Qwen3-MoE configuration that holds a share
of its experts: the program's model and serving engine, built by the
launcher's own calls, holding the benchmark's weights.

This is the one file of the benchmark that knows the program's MoE
parameter tree.  The weights are made by
:func:`bench.models.qwen_moe.make_weights` and laid into that tree inside
the same jitted call.  The program's ``ModelConfig`` is told which experts
it holds (``first_held_expert``, ``n_held_experts``), so a program without
that layer fails here, before anything is allocated.
"""
from __future__ import annotations

import jax

from bench.models import qwen_moe as R

# the program's rmsnorm epsilon (``repro.models.layers.rmsnorm``)
PROGRAM_RMS_EPS = 1e-6


def model_config(c: dict):
    """The program's ``ModelConfig`` for config file ``c``."""
    from repro.configs import get_config
    m = R.dims(c)
    if m["eps"] != PROGRAM_RMS_EPS:
        raise ValueError(f"rms_norm_eps {m['eps']} differs from the "
                         f"program's {PROGRAM_RMS_EPS}")
    return get_config(c["program_arch"]).replace(
        n_layers=m["L"], d_model=m["d"], n_heads=m["hq"], n_kv_heads=m["hkv"],
        head_dim=m["hd"], d_ff=m["f"], vocab=m["v"], qkv_bias=False,
        qk_norm=True, rope_theta=m["theta"], dtype=c["torch_dtype"],
        n_experts=m["E"], top_k=m["k"], expert_d_ff=m["f"],
        dense_residual=False, first_held_expert=m["lo"],
        n_held_experts=m["H"])


def to_program(c: dict, w, padded_vocab: int):
    """The benchmark's weights in the program's parameter tree."""
    import jax.numpy as jnp
    m = R.dims(c)
    L, d, hq, hkv, hd = m["L"], m["d"], m["hq"], m["hkv"], m["hd"]
    lay = w["layers"]
    pad = padded_vocab - m["v"]
    return {
        "embed": {"table": jnp.pad(w["embed"], ((0, pad), (0, 0)))},
        "blocks": {
            "ln1": {"scale": lay["ln1"]},
            "attn": {"wq": lay["wq"].reshape(L, d, hq, hd),
                     "wk": lay["wk"].reshape(L, d, hkv, hd),
                     "wv": lay["wv"].reshape(L, d, hkv, hd),
                     "wo": lay["wo"].reshape(L, hq, hd, d),
                     "q_norm": {"scale": lay["q_norm"]},
                     "k_norm": {"scale": lay["k_norm"]}},
            "ln2": {"scale": lay["ln2"]},
            "moe": {"router": lay["router"], "gate": lay["wg"],
                    "up": lay["wu"], "down": lay["wd"]}},
        "final_norm": {"scale": w["final_norm"]},
        "unembed": {"w": jnp.pad(w["head"], ((0, 0), (0, pad)))},
    }


def build_engine(c: dict, engine_knobs: dict, seed: int, *, tp: int = 1):
    """``ServeEngine`` serving config ``c`` with the seed's weights, on one
    chip (``tp`` 1: the share is one chip's).

    ``engine_knobs`` are the deployment's settings (slots, lengths, pool);
    every knob not named keeps the program's default."""
    from repro.models.api import build_model
    from repro.serve import ServeEngine
    if tp != 1:
        raise ValueError(f"{c['name']} is one chip's share; tp={tp}")
    cfg = model_config(c)
    model = build_model(cfg)
    # parameters stored in the architecture's param_dtype (bfloat16 here)
    want = jax.eval_shape(lambda k: model.init(k, dtype=cfg.param_dtype),
                          jax.random.PRNGKey(0))
    make = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda a, s: a.astype(s.dtype),
        to_program(c, R.make_weights(c, key), cfg.padded_vocab), want))
    params = make(R.seed_key(seed))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    need = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != need:
        raise ValueError("benchmark weights do not match the program's "
                         f"parameter tree:\n{got}\nvs\n{need}")
    return ServeEngine(model, params, **engine_knobs)
