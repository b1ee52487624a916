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
import math
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
# the qwen3-moe-235b-a22b-ep16.decode-c128 cell: 128 slots over 9000 pages
# (+ trash), tables of 256 pages as in the decode cell
MOE_B, MOE_PAGES = 128, 9000 + 1


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


def _paged_program(phase, width, one_chip, moe=False, kv_quant=None):
    """A paged program of a cell, its arguments described on the chip, and
    the model's config: ``decode`` (one token a slot), ``verify`` (a
    ``width``-token window a slot) or ``prefill`` (a ``width``-token chunk
    of one slot).  ``moe`` builds the qwen3-moe-235b-a22b share of the MoE
    cell (8 held experts of 128, 128 slots, 9000 pages + trash) as the
    benchmark's configuration file states it; otherwise qwen3-1.7b at the
    decode cell's shapes.  ``kv_quant`` is the pool's KV quantization.  The
    params are the tree the engine holds (:func:`hold_params`)."""
    from repro.configs import get_config
    from repro.models.api import build_model
    from repro.models.module import hold_params
    from repro.serve import placement as PL
    from repro.serve.quant import make_kv_quant

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: _shape(one_chip, a.shape, a.dtype), tree)

    i32 = functools.partial(_shape, one_chip, dtype=jnp.int32)
    if moe:
        import json
        import pathlib
        import sys
        root = pathlib.Path(__file__).resolve().parents[1]
        sys.path.insert(0, str(root))
        from bench.models import qwen_moe_program as SUT
        cfg = SUT.model_config(json.loads(
            (root / "bench/configs/qwen3-moe-235b-a22b-ep16.json")
            .read_text()))
        b, n_pages = MOE_B, MOE_PAGES
    else:
        cfg = get_config("qwen3-1.7b")
        b, n_pages = CELL_B, CELL_PAGES
    model = build_model(cfg)
    params, _ = hold_params(model.param_defs(), on_chip(jax.eval_shape(
        lambda k: model.init(k, dtype=cfg.param_dtype),
        jax.random.PRNGKey(0))), cfg.dtype)
    quant = make_kv_quant(kv_quant)
    storage = on_chip(model.paged_state_specs(n_pages - 1, PAGE, quant))
    place = i32(PL.identity_plan(cfg.n_experts, 1).dispatch_arrays().shape
                if cfg.n_experts else (3, 0))
    if phase == "prefill":
        def program(p, st, row, pg, s0, t, pl):
            return model.paged_prefill_chunk(p, st, row, pg, s0, t, None,
                                             use_pallas=True, quant=quant,
                                             placement=pl)
        args = (i32((CELL_P,)), i32((width // PAGE,)), i32(()),
                i32((1, width)))
    else:
        # decode is the one-token window, its (B,) targets expanded inside
        write = (b,) if phase == "decode" else (b, width)

        def program(p, st, tb, ln, t, wp, wo, pl):
            if phase == "decode":
                wp, wo = wp[:, None], wo[:, None]
            return model.paged_verify(p, st, tb, ln, t, wp, wo, None,
                                      use_pallas=True, quant=quant,
                                      placement=pl)
        args = (i32((b, CELL_P)), i32((b,)), i32((b, width)), i32(write),
                i32(write))
    return program, (params, storage) + args + (place,), cfg


# Each case's temporaries while the pool was the layer scan's xs/ys (with
# a second copy of the pool), as counted here
PAGED_PROGRAMS = {
    "decode": ("decode", 1, False, None, 4_839_432_192),
    "verify": ("verify", 4, False, None, 4_839_561_728),
    "prefill": ("prefill", 64, False, None, 4_839_367_680),
    "prefill-one-page": ("prefill", PAGE, False, None, 4_875_445_760),
    "moe-decode": ("decode", 1, True, None, 4_130_356_224),
    "moe-prefill": ("prefill", 256, True, None, 4_720_374_784),
    "moe-decode-int8": ("decode", 1, True, "int8", 2_121_774_080),
}


def _hlo_ops(text):
    """(name, dims, opcode) of each array-valued instruction in HLO text."""
    return [(name, [int(x) for x in dims.split(",")], op) for name, dims, op
            in re.findall(r"(%[\w.\-]+) = \w+\[([\d,]+)\]\S* ([\w\-]+)\(",
                          text)]


@pytest.mark.parametrize("case", list(PAGED_PROGRAMS))
def test_decode_step_runs_the_kernel_once_per_layer(one_chip, monkeypatch,
                                                    case):
    """A paged program compiled whole with the kernel, at a cell's shapes:
    one ``tpu_custom_call``, inside the layer scan's body; no gather
    larger than the program's token rows (the token embedding's, and the
    MoE dispatch's rows of its held experts), so none of the KV pool; and
    the stacked pool carried through the scan, so no copy or slice of one
    layer's pages or more (fused or bare) and no update of the stacked
    (L, N, ...) pool is left: each layer writes its new rows in place.
    With int8 pages, the scan's body copies, pads or slices no more than a
    layer's scales.  Every program holds at least one pool less of
    temporaries than it did with the pool as the scan's xs/ys."""
    import math

    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    phase, width, moe, kv_quant, temp_xs_ys = PAGED_PROGRAMS[case]
    program, args, cfg = _paged_program(phase, width, one_chip, moe,
                                        kv_quant)
    compiled = jax.jit(program, donate_argnums=(1,)).lower(*args).compile()
    hlo = compiled.as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "while/body" in calls[0], calls
    storage = args[1]
    n_layers, n_pages, page, hkv, d = storage["k"].shape
    layer = math.prod(storage["k"].shape[1:])
    ops = _hlo_ops(hlo)

    gathers = [dims for _, dims, op in ops if op == "gather"]
    assert gathers, "the token embedding is a gather"
    tokens = width * (1 if phase == "prefill" else MOE_B if moe else CELL_B)
    rows = max(cfg.n_experts, 1) * tokens
    for dims in gathers:
        assert math.prod(dims) <= rows * cfg.d_model, dims

    def pool_rows(dims):
        """A result laid out as the pool's rows: (…, Hkv, D) as stored, or
        (…, page·Hkv, D) as the kernel views a page."""
        return dims[-2:] in ([hkv, d], [page * hkv, d])

    moved = [f"{n} = {dims} {op}" for n, dims, op in ops if pool_rows(dims)
             and ((("copy" in f"{n} {op}" or "dynamic-slice" in f"{n} {op}")
                   and math.prod(dims) >= layer)
                  or ("dynamic-update-slice" in f"{n} {op}"
                      and dims[:2] == [n_layers, n_pages]))]
    assert not moved, moved

    if kv_quant:
        # the scales as stored (page, Hkv) or as the kernel's lane rows,
        # padded or not; one layer's, padded, is a small part of the
        # stacked leaf
        lanes = -(-page * hkv // 128) * 128
        scale_rows = ([page, hkv], [1, page * hkv], [1, lanes])
        body = next(c for c in hlo.split("\n\n") if "tpu_custom_call" in c)
        moved = [f"{n} = {dims} {op}" for n, dims, op in _hlo_ops(body)
                 if dims[-2:] in scale_rows
                 and math.prod(dims) >= storage["k_scale"].size // 2
                 and any(w in f"{n} {op}" for w in
                         ("copy", "pad", "slice", "reshape", "transpose"))]
        assert not moved, moved

    pool = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(storage))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= temp_xs_ys - pool, (temp, pool)


@pytest.mark.parametrize("case", ["decode", "verify", "prefill"])
def test_dense_programs_convert_no_weight_stack(one_chip, monkeypatch, case):
    """qwen3-1.7b's programs on the tree the engine holds, its matrices in
    bfloat16: no convert makes a result of one layer's MLP matrix or more
    (held in float32, each weight stack and the head were converted whole
    on every call, and the temporaries held the bfloat16 copies: 2.8 GB),
    and the temporaries are below one bfloat16 MLP stack."""
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    phase, width, moe, kv_quant, _ = PAGED_PROGRAMS[case]
    program, args, cfg = _paged_program(phase, width, one_chip, moe,
                                        kv_quant)
    compiled = jax.jit(program, donate_argnums=(1,)).lower(*args).compile()
    layer_mlp = cfg.d_model * cfg.d_ff
    converts = [f"{n} = {dims} {op}" for n, dims, op
                in _hlo_ops(compiled.as_text())
                if "convert" in f"{n} {op}" and math.prod(dims) >= layer_mlp]
    assert not converts, converts
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < cfg.n_layers * layer_mlp * 2, temp


@pytest.mark.parametrize("case", ["moe-decode", "moe-prefill"])
def test_moe_programs_are_those_of_the_stored_tree(one_chip, monkeypatch,
                                                   case):
    """The MoE share stores its parameters in bfloat16 already: the tree the
    engine holds is the stored one, and so are its programs."""
    from repro.kernels import ops as kops
    from repro.models.api import build_model
    from repro.models.module import hold_params
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    phase, width, moe, kv_quant, _ = PAGED_PROGRAMS[case]
    program, args, cfg = _paged_program(phase, width, one_chip, moe,
                                        kv_quant)
    model = build_model(cfg)
    stored = jax.tree_util.tree_map(
        lambda a: _shape(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: model.init(k, dtype=cfg.param_dtype),
                       jax.random.PRNGKey(0)))
    held, cast = hold_params(model.param_defs(), stored, cfg.dtype)
    assert cast == 0 and held is stored
    assert jax.tree_util.tree_map(lambda a: a.dtype, args[0]) \
        == jax.tree_util.tree_map(lambda a: a.dtype, stored)
    lowered = [jax.jit(program, donate_argnums=(1,)).lower(
        p, *args[1:]).as_text(debug_info=False) for p in (args[0], stored)]
    assert lowered[0] == lowered[1]


def test_rmsnorm_compiles_for_v5e(one_chip):
    x = _shape(one_chip, (B * 64, 2048), jnp.bfloat16)
    s = _shape(one_chip, (2048,), jnp.float32)
    compiled = jax.jit(RN.rmsnorm).lower(x, s).compile()
    assert "tpu_custom_call" in compiled.as_text()
