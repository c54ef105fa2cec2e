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

Page layout (int4), stacked over the model's ``n_super`` layers:
``kp``/``vp (n_super, P, page_size*ppr, g//2) u8`` packed nibbles and one
``sz (n_super, P, 4, page_size*ppr) f32`` holding, per page row, K's
scale, K's zero, V's scale and V's zero (rows ``SZ_ROWS`` of
``models/paged.py``), lane-dense. ``g`` is the wire's position-aligned
quantization group (g | Hkv*hd) and ``ppr = Hkv*hd // g`` groups per
token. Row ``t*ppr + r`` of a page holds token ``t``'s r-th group, so a
page row-range is exactly a token range — the same row order the wire's
flattened ``(L*len*ppr, g)`` quantization produces. The kernel takes the
stacked arrays whole, with the layer as a scalar-prefetch operand: each
page operand's block is ``(1, 1, ...)`` at ``(layer, page, 0, ...)`` and
its trailing dims are the array's, so the layout the pool is carried in
is the one the kernel reads, and nothing copies a layer's pool out
before the call. The operands are held to HBM, so XLA does not stage a
pool small enough for VMEM through it around each launch. It reads the
packed pages as ``(g//2, page_size*ppr)``, rows on lanes: where a page's
rows fill whole 128-lane tiles, that is how a TPU lays ``kp``/``vp``
out, and the transpose is a bitcast. The nibbles then interleave down
the sublanes and each row's scale and zero apply from their lanes, with
no transpose of ``sz``.

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


# a residency's pool operands, as the cache names them
INT4_OPERANDS = ("kp", "vp", "sz")
BF16_OPERANDS = ("k", "v")


def _keys_as_columns(g, hd, rows):
    """Whether an int4 block's keys stay as its dequantized columns:
    where a group is one head, page row ``t*Hkv + h`` is head ``h`` of
    token ``t``, and a block of whole 128-lane pages needs no transpose."""
    return g == hd and rows % 128 == 0


def _load_int4(pages, *, tokens, Hkv, hd):
    """Dequantize a block. Its packed pages arrive as ``(g//2, ps*ppr)``
    (the pool's lane-dense layout, rows on lanes); the nibbles interleave
    down the sublanes to ``(g, rows)`` f32 and each row's scale and zero,
    a lane of the ``(4, rows)`` ``sz`` block, apply as ``x * scale +
    zero``. Where a group is one head (``_keys_as_columns``) the block's
    keys stay as these ``(hd, tokens*Hkv)`` columns; otherwise each page
    transposes to rows and the block reshapes to ``(tokens*Hkv, hd)``.
    Nibble order comes from the shared layout contract
    (``kernels/kv_layout.py``, rule R005): element 2j in the low nibble of
    byte j, 2j+1 high."""
    kp, vp, sz = pages
    g, R = 2 * kp[0].shape[2], kp[0].shape[3]
    cols = _keys_as_columns(g, hd, R)

    def dequant(packed, c):
        xs = [kv_layout.interleave_nibbles_mxu_t(p[0, 0]) * z[0, 0, c:c + 1]
              + z[0, 0, c + 1:c + 2] for p, z in zip(packed, sz)]
        if cols:
            return jnp.concatenate(xs, axis=1)
        return jnp.concatenate([x.T for x in xs]).reshape(tokens * Hkv, hd)
    return cols, dequant(kp, 0), dequant(vp, 2)


def _load_bf16(pages, *, tokens, Hkv, hd):
    return False, *(jnp.concatenate([r[0, 0] for r in refs]).astype(
        jnp.float32).reshape(tokens * Hkv, hd) for refs in pages)


def _limit(kv_len, page_size, W):
    """Valid keys of a slot: no more than its table holds."""
    return jnp.minimum(kv_len, W * page_size)


def _n_blocks(limit, page_size, ppb):
    """Steps a slot takes: its blocks that hold context, one at least."""
    return jnp.maximum(pl.cdiv(pl.cdiv(limit, page_size), ppb), 1)


def _kernel(slot_ref, blk_ref, page_ref, len_ref, layer_ref, q_ref, *refs,
            load, n_ops, sm_scale, page_size, ppb, W, Hkv):
    """One block of one slot. Each of the ``M = Hkv*gq`` query rows
    scores every key of the block, ``n = t*Hkv + h``, and keeps those of
    its own KV head that hold context: where the block's keys stay as
    columns that is one plain matmul and no transpose."""
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

    q = q_ref[0].astype(jnp.float32)            # (M, hd), m = h*gq + i
    M, hd = q.shape
    tokens = ppb * page_size
    cols, k, v = load(pages, tokens=tokens, Hkv=Hkv, hd=hd)
    N = tokens * Hkv
    start = j * tokens
    # a page past the context holds whatever its operand held before:
    # zero its values so nothing reaches the sum through p == 0
    key_axis = 1 if cols else 0
    v_tok = start + jax.lax.broadcasted_iota(jnp.int32, v.shape,
                                             key_axis) // Hkv
    v = jnp.where(v_tok < limit, v, 0.0)
    s = jax.lax.dot_general(q, k, (((1,), (0 if cols else 1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    n = jax.lax.broadcasted_iota(jnp.int32, (M, N), 1)
    row_head = jax.lax.broadcasted_iota(jnp.int32, (M, N), 0) // (M // Hkv)
    s = jnp.where((n % Hkv == row_head) & (start + n // Hkv < limit), s,
                  NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (1 if cols else 0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == _n_blocks(limit, page_size, ppb) - 1)
    def _done():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
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


def paged_decode_attention(q, pages, page_table, kv_len, layer, *,
                           page_size, sm_scale=None, interpret=False):
    """q: (B, Hkv, gq, hd); page_table: (B, W) int32 (entry 0 = the trash
    page, see ``serving/page_pool.py``); kv_len: (B,) valid lengths;
    layer: the index into the pool's stacked layer axis.

    ``pages`` is one attention slot's stacked pool as the cache holds it:
    int4 ``{"kp", "vp", "sz"}`` (the layout above) or bf16 ``{"k", "v"}``
    of ``(n_super, P, page_size, Hkv, hd)``. Returns (B, Hkv, gq, hd).
    """
    B, Hkv, gq, hd = q.shape
    M = Hkv * gq
    W = page_table.shape[1]
    sm_scale = sm_scale or 1.0 / math.sqrt(hd)
    if "kp" in pages:
        names, load = INT4_OPERANDS, _load_int4
    else:
        names, load = BF16_OPERANDS, _load_bf16
    # the packed pages as the kernel reads them, rows on lanes: on a TPU
    # the pool's own layout when a page's rows fill whole 128-lane tiles
    operands = [jnp.swapaxes(pages[k], 2, 3) if k in ("kp", "vp")
                else pages[k] for k in names]
    ppb = pages_per_block(page_size, W,
                          [((1, 1) + a.shape[2:], a.dtype) for a in operands])
    n_steps, slot, blk, page = _schedule(
        page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
        page_size=page_size, ppb=ppb)
    kernel = functools.partial(_kernel, load=load, n_ops=len(operands),
                               sm_scale=sm_scale, page_size=page_size,
                               ppb=ppb, W=W, Hkv=Hkv)

    n = slot.shape[0]
    if not interpret:
        # the pool stays in HBM: left free, XLA stages a pool that fits
        # VMEM (Command-R's ``sz``) through it around every launch
        operands = [pltpu.with_memory_space_constraint(a, pltpu.HBM)
                    for a in operands]

    def page_spec(a, i):
        return pl.BlockSpec((1, 1) + a.shape[2:],
                            lambda t, s, j, pg, ln, lay: (
                                lay[0], pg[i * n + t]) + (0,) * (a.ndim - 2))

    q_spec = pl.BlockSpec((1, M, hd), lambda t, s, *_: (s[t], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_steps,),
        in_specs=[q_spec] + [page_spec(a, i) for a in operands
                             for i in range(ppb)],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((M, 1), jnp.float32),
                        pltpu.VMEM((M, 1), jnp.float32),
                        pltpu.VMEM((M, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, M, hd), q.dtype),
        interpret=interpret,
    )(slot, blk, page, kv_len.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q.reshape(B, M, hd),
      *[a for a in operands for _ in range(ppb)])
    return out.reshape(B, Hkv, gq, hd)
