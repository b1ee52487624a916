"""Per-kernel allclose tests: Pallas (interpret=True on CPU) vs ref oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 64), (3, 37, 128), (1, 256), (257, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x = _arr(shape, dtype)
    s = _arr(shape[-1:], jnp.float32)
    got = ops.rmsnorm(x, s)
    want = ref.rmsnorm(x, s)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Sq,Sk,Hq,Hkv,D,causal,window", [
    (2, 256, 256, 4, 2, 32, True, None),     # GQA causal
    (1, 512, 512, 2, 2, 16, True, 100),      # sliding window
    (2, 256, 256, 4, 1, 32, False, None),    # bidirectional, MQA
    (1, 128, 128, 8, 8, 64, True, None),     # MHA
    (1, 384, 384, 2, 1, 32, True, 64),       # window + GQA, 3 tiles
])
def test_flash_attention_vs_ref(B, Sq, Sk, Hq, Hkv, D, causal, window):
    q = _arr((B, Sq, Hq, D))
    k = _arr((B, Sk, Hkv, D))
    v = _arr((B, Sk, Hkv, D))
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              q_blk=128, kv_blk=128)
    want = ref.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    q = _arr((1, 256, 4, 32), jnp.bfloat16)
    k = _arr((1, 256, 2, 32), jnp.bfloat16)
    v = _arr((1, 256, 2, 32), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, q_blk=128, kv_blk=128)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_attention_matches_model_attention():
    """Kernel == the models' jnp online-softmax path (the runtime fallback)."""
    from repro.models.attention import gqa_attention
    q = _arr((2, 256, 4, 32))
    k = _arr((2, 256, 2, 32))
    v = _arr((2, 256, 2, 32))
    a = ops.flash_attention(q, k, v, causal=True, q_blk=128, kv_blk=128)
    b = gqa_attention(q, k, v, causal=True, kv_chunk=64)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# rwkv6 scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,K,V,t_blk", [
    (2, 128, 3, 16, 16, 32),
    (1, 64, 2, 8, 8, 64),     # single tile
    (1, 192, 1, 32, 16, 64),  # K != V
])
def test_rwkv6_scan(B, S, H, K, V, t_blk):
    r = _arr((B, S, H, K))
    k = _arr((B, S, H, K), scale=0.3)
    v = _arr((B, S, H, V))
    w = jnp.asarray(RNG.uniform(0.8, 0.999, (B, S, H, K)), jnp.float32)
    u = _arr((H, K))
    s0 = _arr((B, H, K, V), scale=0.1)
    y1, f1 = ops.rwkv6_scan(r, k, v, w, u, s0, t_blk=t_blk)
    y2, f2 = ref.rwkv6_scan(r, k, v, w, u, s0)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(f1, f2, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ssd (mamba-2) scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,q_blk", [
    (2, 256, 3, 16, 8, 64),
    (1, 128, 2, 32, 16, 128),  # single chunk
    (1, 512, 1, 8, 4, 32),     # many chunks
])
def test_ssd_scan(B, S, H, P, N, q_blk):
    xdt = _arr((B, S, H, P), scale=0.1)
    la = jnp.asarray(np.log(RNG.uniform(0.8, 0.999, (B, S, H))), jnp.float32)
    Bm = _arr((B, S, N), scale=0.3)
    Cm = _arr((B, S, N), scale=0.3)
    s0 = _arr((B, H, N, P), scale=0.1)
    y1, f1 = ops.ssd_scan(xdt, la, Bm, Cm, s0, q_blk=q_blk)
    y2, f2 = ref.ssd_scan(xdt, la, Bm, Cm, s0)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(f1, f2, rtol=1e-4, atol=1e-4)


def test_ssd_kernel_matches_model_chunked_path():
    """Kernel == the zamba model's jnp chunked SSD implementation."""
    from repro.models.mamba2 import _ssd_chunked
    B, S, H, P, N = 2, 256, 3, 16, 8
    xdt = _arr((B, S, H, P), scale=0.1)
    la = jnp.asarray(np.log(RNG.uniform(0.8, 0.999, (B, S, H))), jnp.float32)
    Bm = _arr((B, S, N), scale=0.3)
    Cm = _arr((B, S, N), scale=0.3)
    y1, f1 = ops.ssd_scan(xdt, la, Bm, Cm, q_blk=64)
    dt = jnp.ones((B, S, H))
    y2, f2 = _ssd_chunked(xdt, dt, jnp.exp(la), Bm, Cm, chunk=64)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(f1, f2, rtol=1e-4, atol=1e-4)


def test_rwkv_kernel_matches_model_time_mix_recurrence():
    """Kernel recurrence == rwkv6 model block's lax.scan recurrence."""
    B, S, H, K = 1, 64, 2, 16
    r = _arr((B, S, H, K)); k = _arr((B, S, H, K), scale=0.3)
    v = _arr((B, S, H, K)); u = _arr((H, K))
    w = jnp.asarray(RNG.uniform(0.9, 0.999, (B, S, H, K)), jnp.float32)

    def model_step(S_, xs):
        r_t, k_t, v_t, w_t = xs
        kv = k_t[..., :, None] * v_t[..., None, :]
        y = jnp.einsum("bhk,bhkv->bhv", r_t, S_ + u[..., None] * kv)
        S_ = w_t[..., None] * S_ + kv
        return S_, y

    xs = (r.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
          w.swapaxes(0, 1))
    fin, ys = jax.lax.scan(model_step, jnp.zeros((B, H, K, K)), xs)
    y_kernel, fin_kernel = ops.rwkv6_scan(r, k, v, w, u, t_blk=32)
    np.testing.assert_allclose(y_kernel, ys.swapaxes(0, 1), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(fin_kernel, fin, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# paged attention (decode through a page table)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Hq,Hkv,D,ps,N,P", [
    (3, 4, 2, 16, 8, 12, 4),      # GQA
    (2, 4, 1, 32, 16, 6, 3),      # MQA
    (1, 8, 8, 64, 8, 4, 2),       # MHA
    (4, 2, 2, 16, 4, 20, 8),      # many small pages
])
def test_paged_attention_vs_ref(B, Hq, Hkv, D, ps, N, P):
    q = _arr((B, Hq, D))
    kp = _arr((N, ps, Hkv, D))
    vp = _arr((N, ps, Hkv, D))
    tables = jnp.asarray(RNG.integers(0, N, size=(B, P)), jnp.int32)
    # ragged validity lengths, incl. a full table and a partial last page
    lens = RNG.integers(1, P * ps + 1, size=B)
    lens[0] = P * ps
    lengths = jnp.asarray(lens, jnp.int32)
    got = ops.paged_attention(q, kp, vp, tables, lengths)
    want = ref.paged_attention(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_paged_attention_dead_slot_is_zero():
    """length-0 rows (retired decode slots) must emit exact zeros, not a
    softmax over garbage."""
    q = _arr((2, 4, 16))
    kp = _arr((6, 8, 2, 16))
    vp = _arr((6, 8, 2, 16))
    tables = jnp.zeros((2, 3), jnp.int32)
    lengths = jnp.asarray([0, 5], jnp.int32)
    got = np.asarray(ops.paged_attention(q, kp, vp, tables, lengths))
    assert np.all(got[0] == 0.0)
    assert np.any(got[1] != 0.0)


def test_paged_attention_matches_contiguous_flash():
    """A page table laid out contiguously must reproduce plain decode
    attention on the equivalent dense cache."""
    B, Hq, Hkv, D, ps = 2, 4, 2, 16, 8
    P = 4
    S = P * ps
    k = _arr((B, S, Hkv, D))
    v = _arr((B, S, Hkv, D))
    q = _arr((B, 1, Hq, D))
    lengths = jnp.asarray([S, 19], jnp.int32)
    # scatter the dense cache into per-sequence pages
    kp = k.reshape(B * P, ps, Hkv, D)
    vp = v.reshape(B * P, ps, Hkv, D)
    tables = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    got = ops.paged_attention(q[:, 0], kp, vp, tables, lengths)
    from repro.models.attention import gqa_attention
    want = gqa_attention(q, k, v, causal=True, q_offset=lengths - 1,
                         kv_valid_len=lengths, kv_chunk=S)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_paged_attention_bf16():
    q = _arr((2, 4, 16), jnp.bfloat16)
    kp = _arr((8, 8, 2, 16), jnp.bfloat16)
    vp = _arr((8, 8, 2, 16), jnp.bfloat16)
    tables = jnp.asarray(RNG.integers(0, 8, size=(2, 3)), jnp.int32)
    lengths = jnp.asarray([24, 7], jnp.int32)
    got = ops.paged_attention(q, kp, vp, tables, lengths)
    want = ref.paged_attention(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# paged multi-query attention (decode / spec-verify / chunked-prefill windows)
# ---------------------------------------------------------------------------

def _block_tokens(ps, P):
    from repro.kernels.paged_attention import _block_pages
    return _block_pages(ps, P) * ps


def _int8_pages(kp, vp):
    from repro.serve.quant import Int8KVQuant
    quant = Int8KVQuant()
    qk, sk = quant.quantize(kp)
    qv, sv = quant.quantize(vp)
    return qk, qv, sk, sv


# (W, Hq, Hkv, D, page_size, pages N, table width P).  Widths 41 and 20 are
# no multiple of the 16- and 8-page blocks at page sizes 8 and 16.
MQ_CASES = [(W, *shape) for W in (1, 2, 8) for shape in [
    (4, 2, 16, 8, 12, 4),         # GQA, a table narrower than one block
    (4, 1, 32, 16, 6, 3),         # MQA
    (8, 8, 64, 8, 4, 2),          # MHA
]] + [(W, *shape) for W in (1, 2, 64) for shape in [
    (4, 2, 16, 8, 48, 41),        # GQA group 2, three blocks
    (4, 4, 16, 8, 48, 41),        # group 1
    (4, 2, 32, 16, 24, 20),       # page 16: two blocks of 8 pages
]]


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("W,Hq,Hkv,D,ps,N,P", MQ_CASES)
def test_paged_attention_mq_vs_ref(W, Hq, Hkv, D, ps, N, P, quantized):
    """Window kernel vs oracle across ragged per-row offsets: one slot per
    length of interest — nothing cached, one token, a page boundary, a
    block boundary, and a window whose last row reads the table's last
    page to its end — on float or int8 pages with their scales."""
    cap = P * ps - W + 1                     # deepest row ends the table
    lens = np.minimum([0, 1, ps, _block_tokens(ps, P), cap], cap)
    B = len(lens)
    q = _arr((B, W, Hq, D))
    kp = _arr((N, ps, Hkv, D))
    vp = _arr((N, ps, Hkv, D))
    scales = ()
    if quantized:
        kp, vp, *scales = _int8_pages(kp, vp)
    tables = jnp.asarray(RNG.integers(0, N, size=(B, P)), jnp.int32)
    lengths = jnp.asarray(lens, jnp.int32)
    got = ops.paged_attention_mq(q, kp, vp, tables, lengths, *scales)
    want = ref.paged_attention_mq(q, kp, vp, tables, lengths, *scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("W", [1, 4])
def test_paged_attention_mq_reads_live_pages_only(W, quantized):
    """NaN in every page a slot must not read — behind its dead table
    entries and past its window's reach inside its last live page — leaves
    the output finite and bit-identical to the unpoisoned run."""
    Hq, Hkv, D, ps, P = 4, 2, 16, 8, 41      # 16-page blocks, width 41
    lens = [1, 9, 130, 200]                  # reach 1..203+W-1 positions
    B = len(lens)
    live = [-(-(n + W - 1) // ps) for n in lens]
    N = sum(live) + 1                        # + one page all dead entries
    q = _arr((B, W, Hq, D))
    kp = np.asarray(_arr((N, ps, Hkv, D)))
    vp = np.asarray(_arr((N, ps, Hkv, D)))
    tables = np.full((B, P), N - 1, np.int32)
    pages = iter(RNG.permutation(N - 1))
    poison = np.zeros((N, ps), bool)
    poison[N - 1] = True
    for b, (n, m) in enumerate(zip(lens, live)):
        tables[b, :m] = [next(pages) for _ in range(m)]
        tail = (n + W - 1) % ps              # the last page's unread rows
        if tail:
            poison[tables[b, m - 1], tail:] = True
    tables, lengths = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)

    def run(poisoned: bool):
        nan = poison[:, :, None, None] & poisoned
        k = jnp.asarray(np.where(nan, np.nan, kp))
        v = jnp.asarray(np.where(nan, np.nan, vp))
        scales = ()
        if quantized:
            k, v, *scales = _int8_pages(k, v)
            scales = [jnp.where(nan[..., 0], jnp.nan, s) for s in scales]
        return np.asarray(ops.paged_attention_mq(q, k, v, tables, lengths,
                                                 *scales))

    clean = run(False)
    got = run(True)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("D", [16, 128])
@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["gather", "kernel"])
def test_paged_attention_reads_one_layer_of_a_stacked_pool(use_pallas, D,
                                                           quantized):
    """With ``layer``, attention reads that layer's pages of a stacked (L,
    N, ...) pool through the layer's own tables and scales: the same
    output, bit for bit, as the layer's pool handed over alone (a head dim
    of 16 is padded to lanes, 128 is not)."""
    from repro.models.attention import paged_window_attention
    L_, N, ps, Hkv, Hq, W, P = 3, 12, 8, 2, 4, 2, 5
    q = _arr((3, W, Hq, D))
    kp = _arr((L_, N, ps, Hkv, D))
    vp = _arr((L_, N, ps, Hkv, D))
    scales = {}
    if quantized:
        kp, vp, ks, vs = _int8_pages(kp, vp)
        scales = dict(k_scale=ks[1], v_scale=vs[1])
    tables = jnp.asarray(RNG.integers(0, N, size=(3, P)), jnp.int32)
    n_cached = jnp.asarray([0, 9, P * ps - W], jnp.int32)
    got = jax.jit(lambda k, v, layer: paged_window_attention(
        q, k, v, tables, n_cached, layer=layer, use_pallas=use_pallas,
        **scales))(kp, vp, jnp.int32(1))
    want = paged_window_attention(q, kp[1], vp[1], tables, n_cached,
                                  use_pallas=use_pallas, **scales)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_attention_mq_w1_is_bitwise_decode():
    """W=1 must be the decode kernel, bit for bit — the engine's decode
    stream guarantees hang off this equivalence."""
    q = _arr((3, 4, 16))
    kp = _arr((10, 8, 2, 16))
    vp = _arr((10, 8, 2, 16))
    tables = jnp.asarray(RNG.integers(0, 10, size=(3, 4)), jnp.int32)
    lengths = jnp.asarray([0, 7, 32], jnp.int32)
    dec = np.asarray(ops.paged_attention(q, kp, vp, tables, lengths))
    mq = np.asarray(ops.paged_attention_mq(q[:, None], kp, vp, tables,
                                           lengths)[:, 0])
    np.testing.assert_array_equal(dec, mq)


def test_paged_attention_mq_dead_slot_row0_is_zero():
    """length-0 slots (dead decode slots) emit an exact-zero first row;
    deeper rows are never read by the engine."""
    q = _arr((2, 4, 4, 16))
    kp = _arr((6, 8, 2, 16))
    vp = _arr((6, 8, 2, 16))
    tables = jnp.zeros((2, 3), jnp.int32)
    lengths = jnp.asarray([0, 5], jnp.int32)
    got = np.asarray(ops.paged_attention_mq(q, kp, vp, tables, lengths))
    assert np.all(got[0, 0] == 0.0)
    assert np.any(got[1] != 0.0)


def test_paged_attention_mq_trash_page_rows_isolated():
    """Pad rows route their K/V to the pool's trash page (last page id, the
    verify-path convention for short windows / dead slots): whatever lands
    there must not perturb rows whose tables never reference it."""
    B, W, Hq, Hkv, D, ps, P = 2, 4, 4, 2, 16, 8, 3
    N = 7                                    # pages 0..5 live, 6 = trash
    q = _arr((B, W, Hq, D))
    kp = _arr((N, ps, Hkv, D))
    vp = _arr((N, ps, Hkv, D))
    tables = jnp.asarray(RNG.integers(0, N - 1, size=(B, P)), jnp.int32)
    lengths = jnp.asarray([5, ps * P - W + 1], jnp.int32)
    base = np.asarray(ops.paged_attention_mq(q, kp, vp, tables, lengths))
    # trash the trash page — live-row outputs must be bit-identical
    kp2 = kp.at[N - 1].set(1e4)
    vp2 = vp.at[N - 1].set(-1e4)
    got = np.asarray(ops.paged_attention_mq(q, kp2, vp2, tables, lengths))
    np.testing.assert_array_equal(base, got)
    np.testing.assert_allclose(
        base, np.asarray(ref.paged_attention_mq(q, kp, vp, tables, lengths)),
        rtol=2e-4, atol=2e-4)


def test_paged_attention_mq_matches_window_over_contiguous_cache():
    """A contiguous page layout must reproduce the jnp fallback's windowed
    attention on the equivalent dense cache (the model-side oracle used by
    paged_window_attention)."""
    B, W, Hq, Hkv, D, ps = 2, 4, 4, 2, 16, 8
    P = 4
    S = P * ps
    k = _arr((B, S, Hkv, D))
    v = _arr((B, S, Hkv, D))
    q = _arr((B, W, Hq, D))
    n_cached = jnp.asarray([11, S - W], jnp.int32)   # window 0's position
    kp = k.reshape(B * P, ps, Hkv, D)
    vp = v.reshape(B * P, ps, Hkv, D)
    tables = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    got = ops.paged_attention_mq(q, kp, vp, tables, n_cached + 1)
    from repro.models.attention import gqa_attention
    want = gqa_attention(q, k, v, causal=True, q_offset=n_cached,
                         kv_valid_len=n_cached + W, kv_chunk=S)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_paged_attention_mq_bf16():
    q = _arr((2, 3, 4, 16), jnp.bfloat16)
    kp = _arr((8, 8, 2, 16), jnp.bfloat16)
    vp = _arr((8, 8, 2, 16), jnp.bfloat16)
    tables = jnp.asarray(RNG.integers(0, 8, size=(2, 3)), jnp.int32)
    lengths = jnp.asarray([20, 7], jnp.int32)
    got = ops.paged_attention_mq(q, kp, vp, tables, lengths)
    want = ref.paged_attention_mq(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
