"""``chip_smoke.py`` off the chip: its serving phase at a smoke config in
interpret mode, and its refusal to run without a TPU."""
import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs import smoke_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_parity_phase_at_smoke_config():
    """Gather attention and the (interpreted) kernel serve the same traffic;
    the teacher-forced kernel run sees the same schedule, agrees on every
    greedy token and stays inside the script's logit tolerance."""
    cs = _load()
    lines = []
    eng, cmp = cs.kernel_parity(smoke_config("qwen3-1.7b"), n_requests=3,
                                prefix=16, tail=(8, 40), max_new=6, slots=4,
                                max_len=128, prefill_chunk=16,
                                log=lines.append)
    assert eng.use_pallas_attention
    assert max(cmp["max_rel"].values()) <= cs.BF16_RTOL
    assert cmp["greedy_agree"] == 1.0
    assert any("served 3 requests, 18 tokens" in ln for ln in lines)


def test_fan_in_attention_scales_projections_to_fan_in():
    """The tp check's weights: q/k/v/o drawn at std 1/sqrt(fan-in) (d_model
    for q/k/v, q_heads * head_dim for o); every other leaf untouched."""
    import numpy as np
    from repro.launch.serve import init_model
    cs = _load()
    cfg = smoke_config("qwen2-7b")
    _, params = init_model(cfg)
    scaled = cs.fan_in_attention(params, cfg)
    attn = scaled["blocks"]["attn"]
    fan_in = {"wq": cfg.d_model, "wk": cfg.d_model, "wv": cfg.d_model,
              "wo": attn["wo"].shape[-3] * attn["wo"].shape[-2]}
    for name, n in fan_in.items():
        assert abs(float(np.std(attn[name])) * n ** 0.5 - 1) < 0.1, name
    for name, leaf in params["blocks"]["attn"].items():
        if name not in fan_in:
            assert attn[name] is leaf
    assert scaled["embed"] is params["embed"]


@pytest.mark.parametrize("point", ["prefill", "decode4", "all"])
def test_compare_runs_bounds_every_step(point):
    """A logit gap beyond the bound fails the comparison whichever step it
    shows at, not only after prefill and at the 4th decode step."""
    cs = _load()
    ref = cs.TeacherSampler(vocab=8)
    got = cs.TeacherSampler(vocab=8, teacher=ref)
    ref.calls = got.calls = [None]
    got.agree = got.total = 1
    got.max_rel[point] = got.max_rel["all"] = 2 * cs.F32_RTOL
    with pytest.raises(AssertionError, match="logits disagree"):
        cs.compare_runs(ref, got, "tp", cs.F32_RTOL, log=lambda s: None)
    got.max_rel = dict.fromkeys(got.max_rel, cs.F32_RTOL / 2)
    assert cs.compare_runs(ref, got, "tp", cs.F32_RTOL,
                           log=lambda s: None)["greedy_agree"] == 1.0


def test_script_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, timeout=300, env=env, cwd=ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_location(tmp_path, from_env):
    """Importing the entry points leaves JAX's compile cache alone;
    ``enable_compile_cache`` then defers to ``JAX_COMPILATION_CACHE_DIR``
    when it is set and uses ``<repo>/.jax_cache`` otherwise."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(ROOT, ".jax_cache")
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    code = textwrap.dedent(f"""
        import sys, jax
        before = jax.config.jax_compilation_cache_dir
        sys.path.insert(0, {ROOT!r})
        import chip_smoke, repro.launch.serve as launch
        assert jax.config.jax_compilation_cache_dir == before
        assert launch.enable_compile_cache() == {want!r}
        assert jax.config.jax_compilation_cache_dir == {want!r}
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]


def test_compile_cache_needs_a_checkout(tmp_path, monkeypatch):
    """Outside a repository checkout (an installed package) the default has
    no ``<repo>`` to land in: the variable must be set instead."""
    import repro.launch.serve as launch
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(launch, "_SOURCE_ROOT", tmp_path)
    with pytest.raises(RuntimeError, match="JAX_COMPILATION_CACHE_DIR"):
        launch.enable_compile_cache()
