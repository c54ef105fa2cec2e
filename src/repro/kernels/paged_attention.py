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

The grid is ragged: one step per block of ``ppb`` pages that holds
context, summed over the slots (at least one a slot), so a launch's cost
follows the live context and not the table width. Its length and the
step -> (slot, block, pages) schedule are computed from ``kv_len`` ahead
of the call and arrive as scalar-prefetch operands; each step's
BlockSpecs gather the block's pages from the pool. A page past a slot's
context repeats the page its operand held the step before, so the
pipeline copies nothing for it: a launch copies each slot's pages that
hold context, once. Online-softmax state sits in VMEM scratch (same
scheme as ``decode_attention.py``). Both residencies share one body; they
differ only in how a block is loaded (dequantize, or cast).
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
# keys a step aims for: one MXU pass wide
BLOCK_TOKENS = 128
# the pipeline's two buffers of a step's page blocks; half of a v5e's
# default scoped VMEM
BLOCK_VMEM_BYTES = 8 << 20


def _load_int4(pages, *, tokens, Hkv, hd):
    """Dequantize a block: its pages' rows stacked, (ppb*ps*ppr, g//2) u8
    and (ppb*ps*ppr, 1) scale/zero -> (ppb*ps*ppr, g) f32, token-major, so
    each token's flattened Hkv*hd slice -> (tokens, Hkv, hd). Nibble order
    comes from the shared layout contract (``kernels/kv_layout.py``, rule
    R005): element 2j in the low nibble of byte j, 2j+1 high."""
    def dequant(packed, scale, zero):
        rows = lambda refs: jnp.concatenate([r[0] for r in refs])
        x = kv_layout.interleave_nibbles_mxu(rows(packed))
        return (x * rows(scale) + rows(zero)).reshape(tokens, Hkv, hd)
    kp, ks, kz, vp, vs, vz = pages
    return dequant(kp, ks, kz), dequant(vp, vs, vz)


def _load_bf16(pages, *, tokens, Hkv, hd):
    return tuple(jnp.concatenate([r[0] for r in refs]).astype(jnp.float32)
                 for refs in pages)


def _limit(kv_len, page_size, W):
    """Valid keys of a slot: no more than its table holds."""
    return jnp.minimum(kv_len, W * page_size)


def _n_blocks(limit, page_size, ppb):
    """Steps a slot takes: its blocks that hold context, one at least."""
    return jnp.maximum(pl.cdiv(pl.cdiv(limit, page_size), ppb), 1)


def _kernel(slot_ref, blk_ref, page_ref, len_ref, q_ref, *refs, load, n_ops,
            sm_scale, page_size, ppb, W):
    pages = [refs[n * ppb:(n + 1) * ppb] for n in range(n_ops)]
    o_ref, m_scr, l_scr, acc_scr = refs[n_ops * ppb:]
    t = pl.program_id(0)
    b, j = slot_ref[t], blk_ref[t]
    limit = _limit(len_ref[b], page_size, W)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # (Hkv, gq, hd)
    Hkv, gq, hd = q.shape
    tokens = ppb * page_size
    k, v = load(pages, tokens=tokens, Hkv=Hkv, hd=hd)
    start = j * tokens
    # a page past the context holds whatever its operand held before:
    # zero its values so nothing reaches the sum through p == 0
    tok = start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    v = jnp.where(tok < limit, v, 0.0)
    s = jax.lax.dot_general(q, k.transpose(1, 0, 2),
                            (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
    pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(pos < limit, s, NEG_INF)      # (Hkv, gq, tokens)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * corr[..., None] + jax.lax.dot_general(
        p, v.transpose(1, 0, 2), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == _n_blocks(limit, page_size, ppb) - 1)
    def _done():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[..., None]).astype(
                        o_ref.dtype)


def _schedule(page_table, kv_len, *, page_size, ppb):
    """The ragged grid's length and, for each of its ``B * ceil(W/ppb)``
    possible steps, the slot, the block and (flat, operand-major) the pool
    page each of the ``ppb`` page operands reads. A page past its slot's
    context repeats the one its operand read last (the trash page before
    any), so the pipeline, which skips a copy whose block does not change,
    copies only pages that hold context. Steps run along the last axis and
    gathers are flat, so nothing here pads a trailing axis of 1."""
    B, W = page_table.shape
    limit = _limit(kv_len, page_size, W)
    n_live = pl.cdiv(limit, page_size)
    n_blk = _n_blocks(limit, page_size, ppb)
    ends = jnp.cumsum(n_blk)
    n = B * pl.cdiv(W, ppb)
    t = jnp.arange(n, dtype=jnp.int32)
    slot = jnp.minimum((ends[:, None] <= t).sum(0), B - 1)
    blk = t - (ends - n_blk)[slot]
    p = blk * ppb + jnp.arange(ppb, dtype=jnp.int32)[:, None]   # (ppb, n)
    live = (p < n_live[slot]) & (t < ends[-1])
    page = page_table.reshape(-1)[slot * W + jnp.minimum(p, W - 1)]
    last = jax.lax.cummax(jnp.where(live, t, -1), axis=1)
    rows = jnp.arange(ppb, dtype=jnp.int32)[:, None] * n
    page = jnp.where(last >= 0,
                     page.reshape(-1)[rows + jnp.maximum(last, 0)], 0)
    return ends[-1], slot, blk, jnp.maximum(page, 0).reshape(-1)


def _vmem_bytes(shape, dtype) -> int:
    """Bytes of one block in VMEM: its last two dims fill whole
    (sublane, 128-lane) tiles, 8 sublanes of 32 bits."""
    size = jnp.dtype(dtype).itemsize
    *lead, rows, lanes = shape
    sub = 8 * 4 // size
    return (math.prod(lead) * -(-rows // sub) * sub
            * -(-lanes // 128) * 128 * size)


def pages_per_block(page_size: int, table_width: int, page_blocks) -> int:
    """Pages a step attends over: ``BLOCK_TOKENS`` keys, fewer where the
    two buffers of ``page_blocks`` (the ``(shape, dtype)`` of each
    operand's one-page block) would pass ``BLOCK_VMEM_BYTES``, and never
    more than the table holds."""
    page = sum(_vmem_bytes(s, d) for s, d in page_blocks)
    return max(1, min(BLOCK_TOKENS // page_size, table_width,
                      BLOCK_VMEM_BYTES // (2 * page)))


def grid_pages(kv_len, page_size: int) -> int:
    """Pages one launch of ``paged_decode_attention`` copies from the pool
    for the valid lengths ``kv_len``: each slot's pages that hold context
    (an idle slot, of length 1, its trash page). Besides, the pipeline's
    first step fills every page operand, so a first slot of fewer than a
    block's pages adds as many copies of the trash page as it leaves
    empty."""
    return sum(-(-int(n) // page_size) for n in kv_len)


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
    if isinstance(k_pages, (tuple, list)):
        operands, load = (*k_pages, *v_pages), _load_int4
    else:
        operands, load = (k_pages, v_pages), _load_bf16
    ppb = pages_per_block(page_size, W,
                          [((1,) + a.shape[1:], a.dtype) for a in operands])
    n_steps, slot, blk, page = _schedule(
        page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
        page_size=page_size, ppb=ppb)
    kernel = functools.partial(_kernel, load=load, n_ops=len(operands),
                               sm_scale=sm_scale, page_size=page_size,
                               ppb=ppb, W=W)

    n = slot.shape[0]

    def page_spec(a, i):
        return pl.BlockSpec((1,) + a.shape[1:],
                            lambda t, s, j, pg, ln: (pg[i * n + t],)
                            + (0,) * (a.ndim - 1))

    q_spec = pl.BlockSpec((1, Hkv, gq, hd),
                          lambda t, s, *_: (s[t], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_steps,),
        in_specs=[q_spec] + [page_spec(a, i) for a in operands
                             for i in range(ppb)],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((Hkv, gq), jnp.float32),
                        pltpu.VMEM((Hkv, gq), jnp.float32),
                        pltpu.VMEM((Hkv, gq, hd), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, gq, hd), q.dtype),
        interpret=interpret,
    )(slot, blk, page, kv_len.astype(jnp.int32), q,
      *[a for a in operands for _ in range(ppb)])
