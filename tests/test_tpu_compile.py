"""The main path's Pallas kernels compiled for a TPU v5e that is described,
not attached — what interpret mode cannot show: the chip compiler's block
tiling rules and its fast-memory limits.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports every test file.  Shapes are qwen3-1.7b's (16 query / 8 KV heads,
head_dim 128, d_model 2048) and qwen2-7b's per-chip share at tp=4 (7 query /
1 KV head), paged at 16 tokens per page with a 64-token prefill chunk.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_attention as PA
from repro.kernels import rmsnorm as RN

B, PAGE, MAX_LEN, D = 8, 16, 1024, 128
N_PAGES = B * MAX_LEN // PAGE + 1            # pool + trash page


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


@pytest.mark.parametrize("hq,hkv", [(16, 8), (7, 1)])
@pytest.mark.parametrize("window", [1, 64])
@pytest.mark.parametrize("quantized", [False, True])
def test_paged_attention_mq_compiles_for_v5e(one_chip, hq, hkv, window,
                                             quantized):
    kv_dtype = jnp.int8 if quantized else jnp.bfloat16
    args = [_shape(one_chip, (B, window, hq, D), jnp.bfloat16),
            _shape(one_chip, (N_PAGES, PAGE, hkv, D), kv_dtype),
            _shape(one_chip, (N_PAGES, PAGE, hkv, D), kv_dtype),
            _shape(one_chip, (B, MAX_LEN // PAGE), jnp.int32),
            _shape(one_chip, (B,), jnp.int32)]
    if quantized:
        args += [_shape(one_chip, (N_PAGES, PAGE, hkv), jnp.float32)] * 2

        def f(q, k, v, t, n, ks, vs):
            return PA.paged_attention_mq(q, k, v, t, n, k_scale=ks,
                                         v_scale=vs)
    else:
        def f(q, k, v, t, n):
            return PA.paged_attention_mq(q, k, v, t, n)
    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_compiles_for_v5e(one_chip):
    x = _shape(one_chip, (B * 64, 2048), jnp.bfloat16)
    s = _shape(one_chip, (2048,), jnp.float32)
    compiled = jax.jit(RN.rmsnorm).lower(x, s).compile()
    assert "tpu_custom_call" in compiled.as_text()
