"""jit'd public wrappers for the Pallas kernels with backend dispatch.

``backend="auto"`` picks the Pallas TPU kernel on TPU, the interpreted
kernel under tests that request it, and the pure-jnp reference otherwise
(CPU dry-run lowers the jnp path so rooflines reflect XLA:TPU-able HLO, not
an interpreter artifact). A backend that cannot be queried raises: a
silent fallback would hide the device from the run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import kv_quant as _kq
from repro.kernels import paged_attention as _pa
from repro.kernels import ref as _ref
from repro.kernels import rmsnorm as _rn


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _mode(backend: str) -> str:
    if backend == "auto":
        return "pallas" if _on_tpu() else "ref"
    return backend


@functools.partial(jax.jit, static_argnames=("causal", "window", "backend",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=0, backend="auto",
                    block_q=128, block_k=128):
    m = _mode(backend)
    if m == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("backend", "block_k"))
def decode_attention(q, k, v, kv_len, *, backend="auto", block_k=256):
    m = _mode(backend)
    if m == "ref":
        return _ref.decode_attention_ref(q, k, v, kv_len=kv_len)
    return _dec.decode_attention(q, k, v, kv_len, block_k=block_k,
                                 interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("backend", "page_size"))
def paged_decode_attention(q, pages, page_table, kv_len, layer, *,
                           page_size, backend="auto"):
    """Decode attention over a page-table-indirected KV cache; the int4
    residency dequantizes INSIDE the kernel (never materializing a 16-bit
    cache). ``pages``: one attention slot's stacked pool, int4 ``{"kp",
    "vp", "sz"}`` or bf16 ``{"k", "v"}``, read at ``layer``."""
    m = _mode(backend)
    if m == "ref":
        return _ref.paged_decode_attention_ref(q, pages, page_table, kv_len,
                                               layer, page_size=page_size)
    return _pa.paged_decode_attention(q, pages, page_table, kv_len, layer,
                                      page_size=page_size,
                                      interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("backend", "block_n"))
def kv_quant(x, *, backend="auto", block_n=256):
    m = _mode(backend)
    with jax.named_scope("kv_quant"):
        if m == "ref":
            return _ref.kv_quant_ref(x)
        return _kq.kv_quant(x, block_n=block_n, interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("backend", "block_n", "out_dtype"))
def kv_dequant(packed, scale, zero, *, out_dtype=jnp.bfloat16, backend="auto",
               block_n=256):
    m = _mode(backend)
    if m == "ref":
        return _ref.kv_dequant_ref(packed, scale, zero, dtype=out_dtype)
    return _kq.kv_dequant(packed, scale, zero, out_dtype=out_dtype,
                          block_n=block_n, interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("backend", "block_n", "eps"))
def rmsnorm(x, scale, *, eps=1e-6, backend="auto", block_n=256):
    m = _mode(backend)
    if m == "ref":
        return _ref.rmsnorm_ref(x, scale, eps=eps)
    return _rn.rmsnorm(x, scale, eps=eps, block_n=block_n,
                       interpret=(m == "interpret"))
