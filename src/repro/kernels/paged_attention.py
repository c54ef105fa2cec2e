"""Paged flash-decode Pallas kernel with fused int4 dequantization.

One query token per sequence attends over a KV cache that lives in
fixed-size PAGES (``page_size`` tokens each) shared by all sequences; each
sequence's pages are named by a per-slot page table. Two residencies:

* **int4** (the interesting one): pages store the SAME group-wise affine
  int4 encoding the prefill->decode wire uses (``kernels/kv_quant.py``),
  so a transferred ``KVWire`` is scattered straight into pages and the
  dequantization happens HERE, inside the attention inner loop — the
  cache is never materialized in 16-bit.
* **bf16**: pages store plain 16-bit values (ablation / fallback).

Page layout (int4), per layer: ``packed (P, page_size*ppr, g//2) u8`` with
``scale``/``zero (P, page_size*ppr, 1) f32``, where ``g`` is the wire's
position-aligned quantization group (g | Hkv*hd) and ``ppr = Hkv*hd // g``
groups per token. Row ``t*ppr + r`` holds token ``t``'s r-th group, so a
page row-range is exactly a token range — the same row order the wire's
flattened ``(L*len*ppr, g)`` quantization produces.

Grid = (B, n_pages_per_seq) with the page axis innermost; the page table
and per-sequence valid lengths arrive as scalar-prefetch operands, so each
step's BlockSpec index map gathers the right page from HBM and fully
masked pages are skipped. Online-softmax state sits in VMEM scratch
(same scheme as ``decode_attention.py``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import kv_layout

NEG_INF = -1e30


def _dequant_rows(packed, scale, zero):
    """(R, G//2) u8 + (R, 1) scale/zero -> (R, G) f32. Nibble order comes
    from the shared layout contract (``kernels/kv_layout.py``, rule R005):
    element 2j in the low nibble of byte j, 2j+1 high."""
    return kv_layout.interleave_nibbles(packed) * scale + zero


def _accumulate(q, k, v, *, start, kv_len, sm_scale, m_scr, l_scr, acc_scr):
    """One page of online softmax. q (Hkv, gq, hd); k/v (ps, Hkv, hd)."""
    kT = k.transpose(1, 0, 2)                       # (Hkv, ps, hd)
    vT = v.transpose(1, 0, 2)
    s = jax.lax.dot_general(q, kT, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
    pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(pos < kv_len, s, NEG_INF)         # (Hkv, gq, ps)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * corr[..., None] + jax.lax.dot_general(
        p, vT, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _finalize(o_ref, m_scr, l_scr, acc_scr):
    o_ref[0] = (acc_scr[...]
                / jnp.maximum(l_scr[...], 1e-30)[..., None]).astype(
                    o_ref.dtype)


def _kernel_int4(pt_ref, len_ref, q_ref, kp_ref, ks_ref, kz_ref,
                 vp_ref, vs_ref, vz_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 sm_scale, page_size, n_pages, Hkv, hd):
    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[b]
    start = pi * page_size

    @pl.when(start < kv_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        # dequant fused here: rows are token-major, so (ps*ppr, g) -> each
        # token's flattened Hkv*hd slice -> (ps, Hkv, hd)
        k = _dequant_rows(kp_ref[0], ks_ref[0], kz_ref[0]).reshape(
            page_size, Hkv, hd)
        v = _dequant_rows(vp_ref[0], vs_ref[0], vz_ref[0]).reshape(
            page_size, Hkv, hd)
        _accumulate(q, k, v, start=start, kv_len=kv_len, sm_scale=sm_scale,
                    m_scr=m_scr, l_scr=l_scr, acc_scr=acc_scr)

    @pl.when(pi == n_pages - 1)
    def _done():
        _finalize(o_ref, m_scr, l_scr, acc_scr)


def _kernel_bf16(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                 m_scr, l_scr, acc_scr, *, sm_scale, page_size, n_pages):
    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[b]
    start = pi * page_size

    @pl.when(start < kv_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)            # (ps, Hkv, hd)
        v = v_ref[0].astype(jnp.float32)
        _accumulate(q, k, v, start=start, kv_len=kv_len, sm_scale=sm_scale,
                    m_scr=m_scr, l_scr=l_scr, acc_scr=acc_scr)

    @pl.when(pi == n_pages - 1)
    def _done():
        _finalize(o_ref, m_scr, l_scr, acc_scr)


def grid_pages(batch: int, table_width: int) -> int:
    """Page-table entries one launch of ``paged_decode_attention`` covers:
    its grid is ``(batch, table_width)``, one page a step, whether the
    page holds live context or not."""
    return batch * table_width


def paged_decode_attention(q, k_pages, v_pages, page_table, kv_len, *,
                           page_size, sm_scale=None, interpret=False):
    """q: (B, Hkv, gq, hd); page_table: (B, W) int32 (entry 0 = the trash
    page, see ``serving/page_pool.py``); kv_len: (B,) valid lengths.

    ``k_pages``/``v_pages`` are either the int4 triple
    ``(packed (P, page_size*ppr, g//2), scale (P, ..., 1), zero)`` or a
    dense ``(P, page_size, Hkv, hd)`` array. Returns (B, Hkv, gq, hd).
    """
    B, Hkv, gq, hd = q.shape
    W = page_table.shape[1]
    sm_scale = sm_scale or 1.0 / math.sqrt(hd)
    quantized = isinstance(k_pages, (tuple, list))

    def page_ix(b, pi, pt, ln):
        # unallocated table entries are 0 (trash page); clamp defensively
        return (jnp.maximum(pt[b, pi], 0), 0, 0)

    q_spec = pl.BlockSpec((1, Hkv, gq, hd), lambda b, pi, *_: (b, 0, 0, 0))
    out_spec = pl.BlockSpec((1, Hkv, gq, hd), lambda b, pi, *_: (b, 0, 0, 0))
    scratch = [pltpu.VMEM((Hkv, gq), jnp.float32),
               pltpu.VMEM((Hkv, gq), jnp.float32),
               pltpu.VMEM((Hkv, gq, hd), jnp.float32)]

    if quantized:
        kp, ks, kz = k_pages
        vp, vs, vz = v_pages
        R, G2 = kp.shape[1], kp.shape[2]
        kernel = functools.partial(_kernel_int4, sm_scale=sm_scale,
                                   page_size=page_size, n_pages=W,
                                   Hkv=Hkv, hd=hd)
        pk_spec = pl.BlockSpec((1, R, G2), page_ix)
        sc_spec = pl.BlockSpec((1, R, 1), page_ix)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, W),
            in_specs=[q_spec, pk_spec, sc_spec, sc_spec,
                      pk_spec, sc_spec, sc_spec],
            out_specs=out_spec,
            scratch_shapes=scratch,
        )
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, Hkv, gq, hd), q.dtype),
            interpret=interpret,
        )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
          q, kp, ks, kz, vp, vs, vz)

    kernel = functools.partial(_kernel_bf16, sm_scale=sm_scale,
                               page_size=page_size, n_pages=W)
    kd_spec = pl.BlockSpec((1, page_size, Hkv, hd),
                           lambda b, pi, pt, ln: (jnp.maximum(pt[b, pi], 0),
                                                  0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W),
        in_specs=[q_spec, kd_spec, kd_spec],
        out_specs=out_spec,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, gq, hd), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
      q, k_pages, v_pages)
