"""Paged, quantization-resident decode cache format + decode step.

The decode KV cache is a pool of fixed-size PAGES (``page_size`` tokens)
shared by every slot of a decode engine; each slot names its pages through
a per-slot page table row. At rest the pages hold the SAME group-wise
affine int4 encoding the prefill->decode wire uses (``kernels/kv_quant``),
so an arriving ``KVWire`` scatters straight into pages with no dequant
round-trip, and attention dequantizes inside the kernel
(``kernels/paged_attention``). A bf16 residency exists for ablation.

Layout, per attention layer slot (stacked over ``n_super`` superblocks):

* int4: ``kp/vp (n_super, P, page_size*ppr, g//2) u8`` packed nibbles,
  ``ks/vs`` / ``kz/vz (n_super, P, page_size*ppr, 1) f32`` scale/zero.
  ``g = page_group(cfg)`` is the wire's position-aligned quantization
  group (g | Hkv*hd); ``ppr = Hkv*hd // g`` groups per token. Row
  ``t*ppr + r`` of a page is token ``t``'s r-th group — identical row
  order to the wire's flattened ``(L*len*ppr, g)`` quantization.
* bf16: ``k/v (n_super, P, page_size, Hkv, hd)``.

Page 0 is the TRASH page: page-table entries default to 0, and inactive
slots' in-scan tail writes land there (the chunked decode scan keeps
stepping inactive slots with frozen lengths; their garbage K/V must not
hit a page another slot owns). The allocator (``serving/page_pool``)
never hands page 0 out.

``decode_step_paged`` mirrors ``transformer.decode_step_inplace``: a
``fori_loop`` over superblocks, the new token's K/V quantized INLINE
(bit-identical to ``kv_quant_ref``) and scattered into the slot's tail
page, attention via ``ops.paged_decode_attention``. Its device work sits
under named scopes (operation metadata only): ``decode/weights`` (the
layer's slice of the stacked weights), ``decode/qkv``,
``decode/kv_append`` (the tail-page write), ``decode/kv_pages`` (each
layer's pool handed to the kernel), ``decode/paged_attn`` (the kernel and
the output projection), ``decode/ffn`` and ``decode/head``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops
from repro.kernels.ref import kv_quant_ref
from repro.models import layers
from repro.models.layers import dense, norm_apply
from repro.models.transformer import (_apply_ffn, _embed_inputs, head_logits,
                                      slot_kinds)
# the ONE group-selection rule (kernels/kv_layout.py — no cycle): pages
# must pick groups exactly like the wire's padded-extract path or
# zero-copy insertion silently degrades to re-encoding (lint rule R005)
from repro.kernels.kv_layout import pick_group

DEFAULT_PAGE_SIZE = 16


def page_group(cfg) -> int:
    """The quantization group width shared with the wire format: the
    largest candidate dividing Hkv*hd (groups never straddle tokens)."""
    return pick_group(cfg.num_kv_heads * cfg.head_dim)


def groups_per_token(cfg) -> int:
    return (cfg.num_kv_heads * cfg.head_dim) // page_group(cfg)


def table_width(max_seq: int, page_size: int) -> int:
    """Page-table row width: enough entries for a max_seq-token slot."""
    return -(-max_seq // page_size)


def paged_supported(cfg) -> bool:
    """Paged decode covers pure-attention stacks (dense/MoE/VLM): recurrent
    state is O(1) and not paged, ring-buffer SWA caches have their own
    bounded layout, and encoder-decoder (audio) caches are flat arrays.
    Softcap archs keep the dense path (the paged kernel does not apply
    tanh capping)."""
    if cfg.family == "audio" or cfg.sliding_window or cfg.attn_logit_softcap:
        return False
    mixes = {k.split("+")[0] for k in cfg.layer_kinds()}
    return mixes == {"attn"} and page_group(cfg) > 0


def init_paged_cache(cfg, max_slots: int, max_seq: int, num_pages: int, *,
                     page_size: int = DEFAULT_PAGE_SIZE,
                     resident: str = "int4", dtype=jnp.bfloat16):
    """Build the paged decode cache pytree: per-layer page buffers plus
    ``page_table (max_slots, W)`` (all trash) and ``lengths``."""
    assert paged_supported(cfg), cfg.name
    kinds = slot_kinds(cfg)
    n_super = cfg.num_layers // len(kinds)
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    ps = page_size
    cache = {}
    for s in range(len(kinds)):
        if resident == "int4":
            g = page_group(cfg)
            ppr = groups_per_token(cfg)
            R = ps * ppr
            cache[f"slot{s}"] = {
                "kp": jnp.zeros((n_super, num_pages, R, g // 2), jnp.uint8),
                "ks": jnp.zeros((n_super, num_pages, R, 1), jnp.float32),
                "kz": jnp.zeros((n_super, num_pages, R, 1), jnp.float32),
                "vp": jnp.zeros((n_super, num_pages, R, g // 2), jnp.uint8),
                "vs": jnp.zeros((n_super, num_pages, R, 1), jnp.float32),
                "vz": jnp.zeros((n_super, num_pages, R, 1), jnp.float32),
            }
        elif resident == "bf16":
            cache[f"slot{s}"] = {
                "k": jnp.zeros((n_super, num_pages, ps, Hkv, hd), dtype),
                "v": jnp.zeros((n_super, num_pages, ps, Hkv, hd), dtype),
            }
        else:
            raise ValueError(f"unknown residency {resident!r}")
    cache["page_table"] = jnp.zeros((max_slots, table_width(max_seq, ps)),
                                    jnp.int32)
    cache["lengths"] = jnp.zeros((max_slots,), jnp.int32)
    return cache


def decode_step_paged(cfg, params, cache, tokens, *, rt=None,
                      page_size: int = DEFAULT_PAGE_SIZE,
                      backend: str = "auto"):
    """One decode step against the paged pool. tokens: (B, 1).

    Appends each slot's new-token K/V to its tail page (quantized in-loop
    for the int4 residency) and attends through the page table with the
    fused-dequant kernel. Returns (logits, new_cache); only ``lengths``
    advances — page-table mutation is HOST business (admission/release in
    the engine), the device only ever writes through it.
    """
    kinds = slot_kinds(cfg)
    x = _embed_inputs(cfg, params, tokens)
    B = x.shape[0]
    positions = cache["lengths"][:, None]
    n_super = cfg.num_layers // len(kinds)
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    gq = cfg.num_heads // Hkv
    pt = cache["page_table"]
    W = pt.shape[1]
    int4 = "kp" in cache["slot0"]
    if int4:
        g = page_group(cfg)
        ppr = groups_per_token(cfg)
    ps = page_size
    # tail-page coordinates for this step's writes; clamped so a slot at
    # its very last position (or an inactive one) indexes a real table
    # entry — unallocated entries are 0, the trash page
    pos0 = positions[:, 0]
    tail_pi = jnp.minimum(pos0 // ps, W - 1)
    tail_page = jnp.take_along_axis(pt, tail_pi[:, None], axis=1)[:, 0]
    tail_page = jnp.maximum(tail_page, 0)
    tail_off = pos0 % ps
    block_cache = {k: v for k, v in cache.items()
                   if k not in ("lengths", "page_table")}

    def body(i, carry):
        x, bc = carry
        with jax.named_scope("decode/weights"):
            blk = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                a, i, 0, keepdims=False), params["blocks"])
        for s, kind in enumerate(kinds):
            p = blk[f"slot{s}"]
            with jax.named_scope("decode/qkv"):
                h = norm_apply(cfg, p["norm1"], x)
                q, k, v = layers.attn_qkv(cfg, p["attn"], h, positions)
            slot = bc[f"slot{s}"]
            if int4:
                with jax.named_scope("decode/kv_append"):
                    # append-path quantization IS the wire/ref kernel math
                    # (kv_quant_ref), so page contents are bit-identical
                    # no matter which path wrote a token
                    kq, ksc, kzp = kv_quant_ref(k[:, 0].reshape(B * ppr, g))
                    vq, vsc, vzp = kv_quant_ref(v[:, 0].reshape(B * ppr, g))
                    rows = tail_off[:, None] * ppr + jnp.arange(ppr)[None]
                    pg = tail_page[:, None]
                    for name, val, width in (
                            ("kp", kq, g // 2), ("ks", ksc, 1),
                            ("kz", kzp, 1), ("vp", vq, g // 2),
                            ("vs", vsc, 1), ("vz", vzp, 1)):
                        slot[name] = slot[name].at[i, pg, rows].set(
                            val.reshape(B, ppr, width).astype(
                                slot[name].dtype))
                with jax.named_scope("decode/kv_pages"):
                    kpages = tuple(lax.dynamic_index_in_dim(
                        slot[n], i, 0, keepdims=False)
                        for n in ("kp", "ks", "kz"))
                    vpages = tuple(lax.dynamic_index_in_dim(
                        slot[n], i, 0, keepdims=False)
                        for n in ("vp", "vs", "vz"))
            else:
                with jax.named_scope("decode/kv_append"):
                    slot["k"] = slot["k"].at[i, tail_page, tail_off].set(
                        k[:, 0].astype(slot["k"].dtype))
                    slot["v"] = slot["v"].at[i, tail_page, tail_off].set(
                        v[:, 0].astype(slot["v"].dtype))
                with jax.named_scope("decode/kv_pages"):
                    kpages = lax.dynamic_index_in_dim(slot["k"], i, 0,
                                                      keepdims=False)
                    vpages = lax.dynamic_index_in_dim(slot["v"], i, 0,
                                                      keepdims=False)
            bc[f"slot{s}"] = slot
            with jax.named_scope("decode/paged_attn"):
                qr = q[:, 0].reshape(B, Hkv, gq, hd)
                o = ops.paged_decode_attention(qr, kpages, vpages, pt,
                                               pos0 + 1, page_size=ps,
                                               backend=backend)
                out = dense(p["attn"]["wo"],
                            o.reshape(B, 1, cfg.q_dim).astype(x.dtype))
            with jax.named_scope("decode/ffn"):
                if cfg.parallel_block and "mlp" in p:
                    x = x + out + layers.mlp_apply(cfg, p["mlp"], h)
                else:
                    x = x + out
                    if kind.split("+")[1] != "none":
                        delta, _ = _apply_ffn(cfg, p, kind, x, rt)
                        x = x + delta
        return (x, bc)

    x, block_cache = lax.fori_loop(0, n_super, body, (x, block_cache))
    with jax.named_scope("decode/head"):
        x = norm_apply(cfg, params["final_norm"], x)
        logits = head_logits(cfg, params, x)
    new_cache = dict(block_cache)
    new_cache["page_table"] = pt
    new_cache["lengths"] = cache["lengths"] + 1
    return logits, new_cache
