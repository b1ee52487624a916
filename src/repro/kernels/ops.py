"""Jitted public wrappers around the Pallas kernels.

On a TPU the kernels run compiled.  On the CPU backend (unit tests) they
execute with ``interpret=True`` — same kernel body, Python evaluation,
bit-compatible blocking — which is how the per-kernel allclose tests
validate them against :mod:`repro.kernels.ref`.  Any other backend raises:
an accelerator that is not a TPU must never fall back to the interpreter
unnoticed.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import rmsnorm as _rn
from repro.kernels import rwkv6_scan as _rw
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels compile for TPU and interpret on CPU only; "
            f"the default backend is {backend!r}")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_blk",
                                             "kv_blk"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    q_blk: int = 256, kv_blk: int = 256):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               q_blk=q_blk, kv_blk=kv_blk,
                               interpret=_interpret())


@jax.jit
def paged_attention(q, k_pages, v_pages, tables, lengths):
    return _pa.paged_attention(q, k_pages, v_pages, tables, lengths,
                               interpret=_interpret())


@jax.jit
def paged_attention_mq(q, k_pages, v_pages, tables, lengths,
                       k_scale=None, v_scale=None, layer=None):
    # k_scale/v_scale: optional (N, page_size, Hkv) int8-page dequant
    # scales, copied beside their pages and applied in VMEM; with
    # ``layer`` the pages are a stacked (L, N, ...) pool read at that layer
    return _pa.paged_attention_mq(q, k_pages, v_pages, tables, lengths,
                                  k_scale=k_scale, v_scale=v_scale,
                                  layer=layer, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("eps", "rows_blk"))
def rmsnorm(x, scale, *, eps: float = 1e-6, rows_blk: int = 256):
    return _rn.rmsnorm(x, scale, eps=eps, rows_blk=rows_blk,
                       interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("t_blk",))
def rwkv6_scan(r, k, v, w, u, state0=None, *, t_blk: int = 64):
    return _rw.rwkv6_scan(r, k, v, w, u, state0, t_blk=t_blk,
                          interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("q_blk",))
def ssd_scan(xdt, la, Bm, Cm, state0=None, *, q_blk: int = 128):
    return _ssd.ssd_scan(xdt, la, Bm, Cm, state0, q_blk=q_blk,
                         interpret=_interpret())
