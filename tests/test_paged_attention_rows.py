"""The paged-attention kernel on windows whose query rows outgrow one step:
a slot's rows split over grid steps of whole KV heads (``ROW_BLOCK``), and
a head's rows scored in chunks of ``CHUNK_ROWS`` — as a prefill chunk of
256 positions at a GQA group of 16 needs on the chip.  The limits are
shrunk here so that small shapes take both paths, in interpret mode,
against the oracle.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import paged_attention as PA

RNG = np.random.default_rng(7)


def _arr(shape):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("row_block,chunk_rows", [
    (32, 16),       # one head a step, each head's 32 rows in two chunks
    (64, 16),       # two heads a step
    (32, 128),      # one head a step, whole heads a chunk
    (4096, 16),     # one step, chunks within a head
])
def test_rows_split_over_steps_and_chunks(monkeypatch, row_block, chunk_rows,
                                          quantized):
    monkeypatch.setattr(PA, "ROW_BLOCK", row_block)
    monkeypatch.setattr(PA, "CHUNK_ROWS", chunk_rows)
    W, Hq, Hkv, D, ps, N, P = 8, 16, 4, 16, 8, 40, 24   # 32 rows a head
    lens = np.asarray([0, 1, 9, 130, P * ps - W + 1])
    B = len(lens)
    q = _arr((B, W, Hq, D))
    kp, vp = _arr((N, ps, Hkv, D)), _arr((N, ps, Hkv, D))
    scales = ()
    if quantized:
        from repro.serve.quant import Int8KVQuant
        quant = Int8KVQuant()
        (kp, sk), (vp, sv) = quant.quantize(kp), quant.quantize(vp)
        scales = (sk, sv)
    tables = jnp.asarray(RNG.integers(0, N, size=(B, P)), jnp.int32)
    lengths = jnp.asarray(lens, jnp.int32)
    got = ops.paged_attention_mq(q, kp, vp, tables, lengths, *scales)
    want = ref.paged_attention_mq(q, kp, vp, tables, lengths, *scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
