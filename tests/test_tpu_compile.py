"""The main path's Pallas kernels compiled for a TPU v5e that is described,
not attached — what interpret mode cannot show: the chip compiler's block
tiling rules and its fast-memory limits.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports every test file.  Shapes are qwen3-1.7b's (16 query / 8 KV heads,
head_dim 128, d_model 2048), qwen2-7b's per-chip share at tp=4 (7 query /
1 KV head) and whisper-tiny's decoder (6 / 6 heads of 64), paged at 16
tokens per page with a 64-token prefill chunk.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_attention as PA
from repro.kernels import rmsnorm as RN

B, PAGE, MAX_LEN, D = 8, 16, 1024, 128
N_PAGES = B * MAX_LEN // PAGE + 1            # pool + trash page
# the qwen3-1.7b.decode cell: 32 slots of 4096 tokens (256 pages each) over
# a pool of 1100 pages (+ trash)
CELL_B, CELL_P, CELL_PAGES = 32, 4096 // PAGE, 1100 + 1


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("hq,hkv,d", [(16, 8, D), (7, 1, D), (6, 6, 64)])
@pytest.mark.parametrize("window", [1, 64])
@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("b,p,n_pages", [
    (B, MAX_LEN // PAGE, N_PAGES), (CELL_B, CELL_P, CELL_PAGES)])
def test_paged_attention_mq_compiles_for_v5e(one_chip, hq, hkv, d, window,
                                             quantized, b, p, n_pages):
    kv_dtype = jnp.int8 if quantized else jnp.bfloat16
    args = [_shape(one_chip, (b, window, hq, d), jnp.bfloat16),
            _shape(one_chip, (n_pages, PAGE, hkv, d), kv_dtype),
            _shape(one_chip, (n_pages, PAGE, hkv, d), kv_dtype),
            _shape(one_chip, (b, p), jnp.int32),
            _shape(one_chip, (b,), jnp.int32)]
    if quantized:
        args += [_shape(one_chip, (n_pages, PAGE, hkv), jnp.float32)] * 2

        def f(q, k, v, t, n, ks, vs):
            return PA.paged_attention_mq(q, k, v, t, n, k_scale=ks,
                                         v_scale=vs)
    else:
        def f(q, k, v, t, n):
            return PA.paged_attention_mq(q, k, v, t, n)
    compiled = jax.jit(f).lower(*args).compile()
    call, = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    # the fast memory the kernel holds, as counted here; the chip's own
    # count has read twice this, against the kernel's 32 MiB limit
    vmem = re.findall(r'"used_scoped_memory_configs":\[\{[^}]*'
                      r'"size":"(\d+)"', call)
    assert vmem and int(vmem[0]) <= 8 * 2**20, vmem


def test_decode_step_runs_the_kernel_once_per_layer(one_chip, monkeypatch):
    """qwen3-1.7b's whole paged decode step at the decode cell's shapes,
    compiled with the kernel: one ``tpu_custom_call``, inside the layer
    scan's body, and no gather of the KV pool (the one gather left is the
    token embedding's)."""
    import math

    from repro.configs import get_config
    from repro.kernels import ops as kops
    from repro.models.api import build_model
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    cfg = get_config("qwen3-1.7b")
    model = build_model(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: _shape(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    storage = on_chip(model.paged_state_specs(CELL_PAGES - 1, PAGE))
    i32 = functools.partial(_shape, one_chip, dtype=jnp.int32)

    def step(p, st, tb, ln, t, wp, wo):
        return model.paged_decode_step(p, st, tb, ln, t, wp, wo, None,
                                       use_pallas=True)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, storage, i32((CELL_B, CELL_P)), i32((CELL_B,)),
        i32((CELL_B, 1)), i32((CELL_B,)), i32((CELL_B,))).compile()
    hlo = compiled.as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "while/body" in calls[0], calls
    gathers = re.findall(r"= \w+\[([\d,]*)\][^ ]* gather\(", hlo)
    assert gathers, "the token embedding is a gather"
    for dims in gathers:
        size = math.prod(int(d) for d in dims.split(","))
        assert size <= CELL_B * cfg.d_model, dims


def test_rmsnorm_compiles_for_v5e(one_chip):
    x = _shape(one_chip, (B * 64, 2048), jnp.bfloat16)
    s = _shape(one_chip, (2048,), jnp.float32)
    compiled = jax.jit(RN.rmsnorm).lower(x, s).compile()
    assert "tpu_custom_call" in compiled.as_text()
